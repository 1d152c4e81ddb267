"""Plain bisection as a light client performs it (light/client.go
verifySkipping, pivot ``trusted + 9/10 * (target - trusted)``,
light/verifier.go VerifyNonAdjacent / VerifyAdjacent), over plain data and
the signature oracle. No batching, no cache, no device.

A pass: a new client trusts ``root`` (its commit's own +2/3), then asks for
``target`` again and again until it is accepted (a relayer that was refused
asks another peer). Each request bisects from the highest height the client
trusts. An attempt from trusted height ``t`` to height ``h``:

1. more than 1/3 of the set of ``t``'s power signed ``h``'s commit, its
   lanes walked in commit order and matched to the set of ``t`` by
   address, from a plain dict (skipping_ref.verify_commit_trusting); too
   little and the client pivots ("cant_trust"), no signature looked at;
2. +2/3 of ``h``'s own set, lanes by index (light_ref.verify_commit_light);
   an adjacent ``h`` (``t + 1``) has this check alone.

What the provider serves follows a script: the ``i``-th fetch of a pass
gets the ``i``-th entry's commit variant when the heights agree, else the
sound commit.

A request's answer is ``(trace, verdict)``: the heights the client came to
trust, in order, and ``("accept", block hash)`` or ``("reject", height,
check, lane)`` naming the first lane whose signature does not verify in
the check that failed (``check`` is ``"trusting"`` or ``"light"``),
``("power", height, check)``, or ``("missing", height)`` when the client
asks for a height the chain has no commit for (the provider has no block:
the pass ends there). Beside it stand the lanes each of its steps had to
verify.
"""

from __future__ import annotations

from . import ed25519_oracle as oracle
from . import light_ref, skipping_ref

PIVOT_NUM, PIVOT_DEN = 9, 10
SOUND = "sound"


class Chain:
    """What the reference reads: per height the set (addresses and
    pubkeys, in set order) and per (height, variant) the commit."""

    def __init__(self, sets: dict, commits: dict, power: int,
                 trust: tuple[int, int]):
        self.sets = sets  # height -> (addresses, pubkeys)
        self.commits = commits  # (height, variant) -> RawCommit
        self.power = power
        self.trust = trust


def light_check(chain: Chain, h: int, variant: str, verify_lanes):
    """``h``'s own +2/3: (verdict, lanes verified)."""
    commit = chain.commits[(h, variant)]
    pubkeys = chain.sets[h][1]
    verdict = light_ref.verify_commit_light(
        commit, pubkeys, chain.power, verify_lanes)
    if verdict[0] == "power":
        return verdict, 0
    return verdict, light_ref.lanes_counted(len(pubkeys), chain.power, 2, 3)


def trusting_by_index(commit, trusted_pubkeys, power, num, den,
                      verify_lanes):
    """The control ``by_index``: the trusting check reads the trusted set
    at the commit's index, as the light check reads its own set."""
    needed = len(trusted_pubkeys) * power * num // den
    tpl = commit.template()
    lanes, tallied = [], 0
    for lane in range(min(len(commit.signatures), len(trusted_pubkeys))):
        lanes.append((trusted_pubkeys[lane], commit.sign_bytes(lane, tpl),
                      commit.signatures[lane]))
        tallied += power
        if tallied > needed:
            break
    if tallied <= needed:
        return ("power", None), 0
    for lane, ok in enumerate(verify_lanes(lanes)):
        if not ok:
            return ("reject", lane), len(lanes)
    return ("accept", None), len(lanes)


def attempt(chain: Chain, trusted: int | None, h: int, variant: str,
            verify_lanes, by_index: bool = False):
    """One attempt: ``("verified", lanes)``, ``("cant_trust", 0)``,
    ``("reject", check, lane, lanes)`` or ``("power", check, lanes)``.
    ``trusted`` None is a client's root: its own +2/3 alone."""
    if trusted is not None and h != trusted + 1:
        commit = chain.commits[(h, variant)]
        t_addrs, t_pubkeys = chain.sets[trusted]
        num, den = chain.trust
        if by_index:
            verdict, n = trusting_by_index(
                commit, t_pubkeys, chain.power, num, den, verify_lanes)
        else:
            verdict, n = skipping_ref.verify_commit_trusting(
                commit, chain.sets[h][0], t_addrs, t_pubkeys, chain.power,
                num, den, verify_lanes)
        if verdict[0] == "power":
            return ("cant_trust", 0)
        if verdict[0] == "reject":
            return ("reject", "trusting", verdict[1], n)
    else:
        n = 0
    verdict, light = light_check(chain, h, variant, verify_lanes)
    if verdict[0] == "reject":
        return ("reject", "light", verdict[1], n + light)
    if verdict[0] == "power":
        return ("power", "light", n)
    return ("verified", n + light)


def walk(fetch, attempt_fn, root: int, target: int, max_requests: int,
         block_hash):
    """The pass: ``fetch(h)`` -> (variant, fetch index) is the provider,
    ``attempt_fn(trusted, h, variant, fetch index)`` judges an attempt (see
    :func:`attempt`), ``block_hash(h, variant)`` names an accepted header.
    Returns the answers: (trace, verdict, lanes of each step)."""
    answers = []
    variant, at = fetch(root)
    out = attempt_fn(None, root, variant, at)
    if out[0] != "verified":
        answers.append(((), _refusal(root, out), [out[-1]]))
        return answers
    answers.append(((root,), ("accept", block_hash(root, variant)),
                    [out[1]]))
    trusted = [root]
    for _ in range(max_requests):
        answer = _request(fetch, attempt_fn, trusted, target, block_hash)
        answers.append(answer)
        if answer[1][0] in ("accept", "missing"):
            break
    return answers


def _refusal(h: int, out):
    if out[0] == "reject":
        return ("reject", h, out[1], out[2])
    return ("power", h, out[1])


def _request(fetch, attempt_fn, trusted: list, target: int, block_hash):
    """One ask for ``target``: light/client.go verifySkipping from the
    highest trusted height. ``trusted`` grows by what it verifies."""
    variant, at = fetch(target)
    cache = [(target, variant, at)]
    depth = 0
    verified = trusted[-1]
    trace: list[int] = []
    lanes: list[int] = []
    while True:
        h, variant, at = cache[depth]
        out = attempt_fn(verified, h, variant, at)
        if out[0] == "missing":
            return tuple(trace), ("missing", h), lanes + [0]
        if out[0] == "cant_trust":
            if depth == len(cache) - 1:
                pivot = verified + (h - verified) * PIVOT_NUM // PIVOT_DEN
                cache.append((pivot, *fetch(pivot)))
            depth += 1
            continue
        if out[0] != "verified":
            return tuple(trace), _refusal(h, out), lanes + [out[-1]]
        lanes.append(out[1])
        trace.append(h)
        trusted.append(h)
        if depth == 0:
            return tuple(trace), ("accept", block_hash(h, variant)), lanes
        verified = h
        del cache[depth:]
        depth = 0


def script_fetch(script: list):
    """The provider's rule over ``script`` = [(height, variant)], one pass:
    fetch ``i`` gets entry ``i``'s variant when the heights agree, else the
    sound commit."""
    cursor = [0]

    def fetch(h: int):
        i = cursor[0]
        cursor[0] += 1
        if i < len(script) and script[i][0] == h:
            return script[i][1], i
        return SOUND, i

    return fetch


def pass_answers(chain: Chain, script: list, root: int, target: int,
                 max_requests: int, verify_lanes=None, by_index=False):
    """What a sound client answers over one pass of ``script``."""
    verify_lanes = verify_lanes or oracle.verify_lanes

    def judge(trusted, h, variant, _at):
        if (h, variant) not in chain.commits:
            return ("missing",)
        return attempt(chain, trusted, h, variant, verify_lanes, by_index)

    return walk(script_fetch(script), judge, root, target, max_requests,
                lambda h, v: chain.commits[(h, v)].block_hash)


# --- each distinct commit's lanes are verified once, in a pool ----------


def lanes_job(job) -> list[bool]:
    """Worker: (commit, pubkeys, lo, hi) -> verdicts of lanes lo..hi-1
    under the commit's own set. Top level so a spawned process can run
    it."""
    commit, pubkeys, lo, hi = job
    tpl = commit.template()
    return [oracle.verify(pubkeys[i], commit.sign_bytes(i, tpl),
                          commit.signatures[i]) for i in range(lo, hi)]


def verified_lanes(chain: Chain, pool=None, slices: int = 4) -> dict:
    """{(pubkey, signature): verdict} of every lane of every commit of
    ``chain``: each commit's lanes once, spread over ``pool``. A
    signature names its lane (its key and the lane's sign-bytes)."""
    jobs = []
    for (h, _variant), commit in chain.commits.items():
        pubkeys = chain.sets[h][1]
        step = -(-len(pubkeys) // slices)
        for lo in range(0, len(pubkeys), step):
            jobs.append((commit, pubkeys, lo, min(lo + step, len(pubkeys))))
    results = (map(lanes_job, jobs) if pool is None
               else pool.map(lanes_job, jobs))
    memo = {}
    for (commit, pubkeys, lo, hi), bits in zip(jobs, results):
        for i, ok in zip(range(lo, hi), bits):
            memo[(pubkeys[i], commit.signatures[i])] = ok
    return memo


def memo_verifier(memo: dict):
    """verify_lanes over the verdicts of :func:`verified_lanes`; a lane it
    has not seen (a control that pairs a signature with another key) goes
    to the oracle."""
    def verify_lanes(lanes):
        out = []
        for pk, msg, sig in lanes:
            ok = memo.get((pk, sig))
            out.append(oracle.verify(pk, msg, sig) if ok is None else ok)
        return out

    return verify_lanes


# --- controls: the reference in the program's place with one guarantee
# broken. ``stride8`` and ``trust_all`` stop checking signatures;
# ``by_index`` reads the trusted set at the commit's index, not by address.


def control_verifier(control: str, verify_lanes):
    if control == "stride8":
        def stride8(lanes):
            bits = [True] * len(lanes)
            bits[::8] = verify_lanes(lanes[::8])
            return bits
        return stride8
    if control == "trust_all":
        return lambda lanes: [True] * len(lanes)
    return verify_lanes


CONTROLS = ("stride8", "trust_all", "by_index")
