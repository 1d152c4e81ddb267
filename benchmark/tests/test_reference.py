"""The plain references against what they stand for."""

import hashlib

from benchmark.drivers import adapters
from benchmark.harness import chain as rawchain
from benchmark.harness import tracing
from benchmark.reference import light_ref, rfc6962


def _chain(n_vals=8, heights=3, seed=11):
    raw = rawchain.make_validators(seed, "val", n_vals)
    vals = adapters.validator_set(raw)
    ch = adapters.HeaderChain("t-chain", heights, vals, seed)
    commits = rawchain.sign_commits(
        raw, "t-chain", [ch.block_tuple(h) for h in range(1, heights + 1)]
    )
    return raw, vals, ch, commits


def test_sign_bytes_equal_the_programs():
    raw, _vals, ch, commits = _chain()
    lb = ch.light_block(commits[2], raw.addresses)
    for lane in range(len(raw)):
        assert (lb.signed_header.commit.vote_sign_bytes("t-chain", lane)
                == commits[2].sign_bytes(lane))


def test_reference_verdicts():
    raw, _vals, _ch, commits = _chain(n_vals=9)
    cut = light_ref.lanes_counted(9, rawchain.VOTING_POWER, 2, 3)
    assert cut == 7
    ok = light_ref.verify_commit_light(commits[1], raw.pubkeys, 10)
    assert ok == ("accept", None)
    bad = rawchain.tamper(commits[1], [5, 2], 11)
    assert light_ref.verify_commit_light(bad, raw.pubkeys, 10) == ("reject", 2)
    past = rawchain.tamper(commits[1], [8], 11)
    assert light_ref.verify_commit_light(past, raw.pubkeys, 10) == ("accept", None)


def test_program_accepts_and_refuses_as_the_reference():
    from cometbft_tpu.types import validation

    raw, vals, ch, commits = _chain(n_vals=9)
    lb = ch.light_block(commits[2], raw.addresses)
    c = lb.signed_header.commit
    validation.verify_commit_light("t-chain", vals, c.block_id, 2, c)
    bad = ch.light_block(rawchain.tamper(commits[2], [3], 11), raw.addresses)
    c = bad.signed_header.commit
    try:
        validation.verify_commit_light("t-chain", vals, c.block_id, 2, c)
    except validation.VerificationError as e:
        assert "(#3)" in str(e)
    else:
        raise AssertionError("an altered commit was accepted")


def test_rfc6962_equals_the_programs_merkle():
    from cometbft_tpu.crypto import merkle

    items = [hashlib.sha256(b"%d" % i).digest() for i in range(13)]
    for n in (0, 1, 2, 3, 7, 13):
        assert rfc6962.root(items[:n]) == merkle.hash_from_byte_slices(items[:n])


def test_trace_reduction_known_answer():
    from benchmark import run as bench_run

    assert bench_run.selfcheck() == 0
    assert tracing._op_name("%fusion.3 = (s32[1]) fusion(...)") == "fusion.3"
