"""Plain skipping verification as a light proxy performs it for one request
(light/client.go initializeWithTrustOptions + light/verifier.go
VerifyNonAdjacent), over plain data and the signature oracle: the root's own
+2/3, then +1/3 of the TRUSTED set on the target's commit (lanes matched by
validator address, types/validation.go VerifyCommitLightTrusting), then the
target's own +2/3. No batching, no cache, no device.

A request's verdict is ``("accept", block hash of the target)``,
``("bad_root", None)`` when the root the caller named is not the chain's,
``("reject", lane)`` naming the first lane whose signature does not verify in
the first check that fails, or ``("power", None)``. Beside it stands the
number of lanes the checks had to verify to reach that verdict.
"""

from __future__ import annotations

from . import ed25519_oracle as oracle
from . import light_ref


def verify_commit_trusting(commit, commit_addresses, trusted_addresses,
                           trusted_pubkeys, power: int, num: int, den: int,
                           verify_lanes=None):
    """More than num/den of the trusted set's power must have signed
    ``commit``: walk the commit's lanes in order, take those whose address
    is in the trusted set, stop once the tally passes. Returns (verdict,
    lanes verified)."""
    needed = len(trusted_pubkeys) * power * num // den
    by_address = {a: i for i, a in enumerate(trusted_addresses)}
    tpl = commit.template()
    lanes, where, tallied = [], [], 0
    for lane, addr in enumerate(commit_addresses):
        i = by_address.get(addr)
        if i is None:
            continue
        lanes.append((trusted_pubkeys[i], commit.sign_bytes(lane, tpl),
                      commit.signatures[lane]))
        where.append(lane)
        tallied += power
        if tallied > needed:
            break
    if tallied <= needed:
        return ("power", None), 0
    bits = (verify_lanes or oracle.verify_lanes)(lanes)
    for lane, ok in zip(where, bits):
        if not ok:
            return ("reject", lane), len(lanes)
    return ("accept", None), len(lanes)


def verify_request(root_commit, target_commit, named_root_hash, addresses,
                   pubkeys, power: int, trust_num: int, trust_den: int,
                   verify_lanes=None):
    """One proxy request over a chain with one validator set: (verdict,
    lanes verified)."""
    if named_root_hash != root_commit.block_hash:
        return ("bad_root", None), 0
    light = light_ref.lanes_counted(len(pubkeys), power, 2, 3)
    verdict = light_ref.verify_commit_light(
        root_commit, pubkeys, power, verify_lanes)
    if verdict[0] != "accept":
        return verdict, light if verdict[0] == "reject" else 0
    verdict, n_trusting = verify_commit_trusting(
        target_commit, addresses, addresses, pubkeys, power,
        trust_num, trust_den, verify_lanes)
    if verdict[0] != "accept":
        return verdict, light + n_trusting
    verdict = light_ref.verify_commit_light(
        target_commit, pubkeys, power, verify_lanes)
    if verdict[0] != "accept":
        return verdict, 2 * light + n_trusting
    return ("accept", target_commit.block_hash), 2 * light + n_trusting


def request_job(job):
    """Worker: the arguments of :func:`verify_request` as one tuple ->
    (verdict, lanes). Top level so that a spawned process can run it."""
    return verify_request(*job)
