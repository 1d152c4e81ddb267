"""``LightService.verify_at_height`` against the benchmark's plain
reference (benchmark/reference/skipping_ref: own sign-bytes encoder, the
``cryptography`` oracle, nothing of the program) on seeded chains: sound
requests, a root hash that is not the chain's, and targets with one counted
lane altered, before and after the +1/3 cut. The service runs as the
``qa175-relayers-backfill`` cell runs it: defaults, its own coalescer."""

from __future__ import annotations

import pytest

from benchmark.drivers import adapters
from benchmark.drivers.light_backfill import verdict_of
from benchmark.harness import chain as rawchain
from benchmark.reference import light_ref, skipping_ref
from cometbft_tpu.light import LightService

N_VALS, HEIGHTS, CHAIN_ID = 13, 60, "ref-chain"
LIGHT = light_ref.lanes_counted(N_VALS, rawchain.VOTING_POWER, 2, 3)
TRUSTING = light_ref.lanes_counted(N_VALS, rawchain.VOTING_POWER, 1, 3)
# (kind, trust height, target height, altered lane of the target or None)
REQUESTS = [
    ("sound", 3, 41, None),
    ("sound", 9, 11, None),
    ("bad_root", 5, 30, None),
    ("bad_target", 7, 52, 0),
    ("bad_target", 12, 33, TRUSTING - 1),
    ("bad_target", 14, 47, TRUSTING),
    ("bad_target", 20, 58, LIGHT - 1),
    ("past_cut", 22, 44, LIGHT),
]


@pytest.fixture(scope="module", params=[7, 2**31 + 99])
def served(request):
    seed = request.param
    raw = rawchain.make_validators(seed, "val", N_VALS)
    vals = adapters.validator_set(raw)
    chain = adapters.HeaderChain(CHAIN_ID, HEIGHTS, vals, seed)
    commits = rawchain.sign_commits(
        raw, CHAIN_ID, [chain.block_tuple(h) for h in range(1, HEIGHTS + 1)])
    for _kind, _trust, target, lane in REQUESTS:
        if lane is not None:
            commits[target] = rawchain.tamper(commits[target], [lane], seed)
    provider = adapters.ChainProvider(chain, commits, raw.addresses)
    svc = LightService(provider, CHAIN_ID, own_coalescer=True)
    svc.start()
    try:
        yield seed, raw, chain, commits, svc
    finally:
        svc.stop()


@pytest.mark.parametrize("kind,trust,target,lane", REQUESTS)
def test_service_answers_what_the_reference_answers(
    served, kind, trust, target, lane
):
    seed, raw, chain, commits, svc = served
    named = (rawchain.seed_bytes(seed, "noroot", trust)
             if kind == "bad_root" else None)
    want, lanes = skipping_ref.verify_request(
        commits[trust], commits[target],
        named or commits[trust].block_hash, raw.addresses, raw.pubkeys,
        rawchain.VOTING_POWER, 1, 3)
    misses0 = svc.cache.stats()["misses"]
    try:
        got = svc.verify_at_height(
            target, trust_height=trust, trust_hash=named,
            now_ns=chain.now_ns())
        got = ("accept", bytes.fromhex(got["hash"]))
    except Exception as e:
        got = verdict_of(e)
    assert got == want
    expected = {
        "sound": ("accept", LIGHT + TRUSTING + LIGHT),
        "bad_root": ("bad_root", 0),
        "past_cut": ("accept", LIGHT + TRUSTING + LIGHT),
    }.get(kind)
    if kind == "bad_target":
        first_check = lane < TRUSTING
        expected = ("reject", LIGHT + TRUSTING + (0 if first_check else LIGHT))
        assert want == ("reject", lane)
    assert (want[0], lanes) == expected
    # one check of the plane per commit check the reference made
    checks = {0: 0, LIGHT + TRUSTING: 2}.get(lanes, 3)
    assert svc.cache.stats()["misses"] - misses0 == checks
    assert svc.cache.stats()["hits"] == 0
