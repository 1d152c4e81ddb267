"""Plain commit verification as a light client requires it
(types/validation.go VerifyCommitLight), over
plain data and the signature oracle. No batching, no cache, no device.

A verdict is ``("accept", None)``, ``("reject", lane)`` naming the first lane
whose signature does not verify, or ``("power", None)`` when the counted
power does not pass the threshold.
"""

from __future__ import annotations

from . import ed25519_oracle as oracle


def lanes_counted(n_vals: int, power: int, num: int, den: int) -> int:
    """How many lanes, walking in order with every validator signing at
    equal power, are taken before the tally passes num/den of the total."""
    needed = n_vals * power * num // den
    return needed // power + 1


def commit_lanes(commit, pubkeys, count: int):
    """(pubkey, sign bytes, signature) of the first ``count`` lanes."""
    tpl = commit.template()
    return [
        (pubkeys[i], commit.sign_bytes(i, tpl), commit.signatures[i])
        for i in range(count)
    ]


def verdict_from_bits(bits: list[bool]):
    for lane, ok in enumerate(bits):
        if not ok:
            return ("reject", lane)
    return ("accept", None)


def verify_commit_light(commit, pubkeys, power: int, verify_lanes=None):
    """+2/3 of the commit's own validator set; stops once reached, so a bad
    signature past the cut is never looked at."""
    count = lanes_counted(len(pubkeys), power, 2, 3)
    if count > len(commit.signatures):
        return ("power", None)
    lanes = commit_lanes(commit, pubkeys, count)
    return verdict_from_bits((verify_lanes or oracle.verify_lanes)(lanes))
