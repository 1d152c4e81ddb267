"""Plain reference for a node catching up a mixed ed25519 + sr25519 chain
by block sync (blocksync/reactor.go poolRoutine: block h is proven by block
h+1's LastCommit, VerifyCommitLight; state/validation.go validateBlock: the
block's own LastCommit in full, VerifyCommit), over plain data and two
signature oracles: the ``cryptography`` wheel for ed25519 lanes and
``sr25519_ref`` for sr25519 lanes. Nothing of the program, no batching, no
device.

Which lanes it verifies, and why the others need not be: every lane of the
light check of every altered block the peers served (2,731 lanes at 4,096
validators), and every lane of both checks (2,731 + 4,096) of a seeded 1 in
16 of the blocks the node applied in the window, at least one. The other
lanes are sound by construction: set-up signed each of them from ``--seed``
over this module's own sign-bytes, and only the altered lanes were changed
after signing; a signer or an encoding that drifted would fail every lane
of every sampled block. Verifying every lane of a 30 s window (~500,000,
half of them sr25519 at ~4 ms each in pure Python) would take the chip
host's cores minutes.

The walk (:func:`walk`) takes what the peers served, height by height in
the order they served it, and decides with upstream's rule which blocks are
applied, which pairs are refused and which peers are removed: the pair
(h, h+1) is refused when block h is not the one h+1's LastCommit commits
(an altered block has another hash) or when that LastCommit's light check
fails; a refused pair's two serving peers are removed and both heights are
taken from their next delivery.
"""

from __future__ import annotations

from . import canonical
from . import ed25519_oracle as oracle
from . import light_ref
from . import sr25519_ref as sr

ED, SR = "ed25519", "sr25519"
L = sr.L
SAMPLE_ONE_IN = 16


def light_lanes(n_vals: int, power: int) -> int:
    """Lanes of VerifyCommitLight with every validator signing at equal
    power: index order, stop once the tally passes 2/3."""
    return light_ref.lanes_counted(n_vals, power, 2, 3)


def template(chain_id: str, height: int, block) -> tuple[bytes, bytes]:
    """``block`` = (hash, part-set total, part-set hash) of the block the
    precommits are for."""
    return canonical.vote_template(chain_id, height, 0, *block)


def sign_bytes(tpl, timestamp_ns: int) -> bytes:
    return canonical.vote_sign_bytes(tpl, timestamp_ns)


def commit_lanes(chain_id: str, height: int, block, schemes, pubkeys,
                 stamps, sigs, count: int):
    """(scheme, pubkey, sign bytes, signature) of the first ``count``
    lanes of a commit for ``block`` at ``height``."""
    tpl = template(chain_id, height, block)
    return [(schemes[i], pubkeys[i], sign_bytes(tpl, stamps[i]), sigs[i])
            for i in range(count)]


def verify_lanes(lanes) -> list[bool]:
    """One verdict per (scheme, pubkey, msg, sig), each under its own
    scheme: ZIP-215 = RFC 8032 on honestly signed and bit-flipped lanes
    (the wheel), schnorrkel v1 with the substrate context."""
    return [oracle.verify(pk, m, s) if scheme == ED else sr.verify(pk, m, s)
            for scheme, pk, m, s in lanes]


# --- controls: a verifier with one guarantee broken, in the program's place


def _stride8(lanes):
    """Spot check: every 8th lane verified, the rest taken on trust."""
    bits = [True] * len(lanes)
    bits[::8] = verify_lanes(lanes[::8])
    return bits


def _trust_all(lanes):
    """Count the power, verify nothing."""
    return [True] * len(lanes)


def _ed_only(lanes):
    """Verify the ed25519 lanes, take every sr25519 lane as sound."""
    return [verify_lanes([lane])[0] if lane[0] == ED else True
            for lane in lanes]


CONTROLS = {"stride8": _stride8, "trust_all": _trust_all,
            "ed_only": _ed_only}


def lanes_job(job):
    """Worker (top level, for a spawn pool): ``(lanes, control)`` ->
    verdicts, by the oracles or by the named control."""
    lanes, control = job
    return (CONTROLS[control] if control else verify_lanes)(lanes)


def commit_faults(bits, power: int, n_vals: int) -> int:
    """Bad lanes of a stored commit's check, plus one if its sound lanes
    carry no more than 2/3 of the set's power."""
    bad = sum(1 for ok in bits if not ok)
    return bad + ((len(bits) - bad) * power <= n_vals * power * 2 // 3)


def sampled(seed_bytes, heights) -> list[int]:
    """The seeded 1 in ``SAMPLE_ONE_IN`` of ``heights`` (at least one)."""
    pick = [h for h in heights
            if seed_bytes("sample", h)[0] % SAMPLE_ONE_IN == 0]
    return pick or list(heights[:1])


def walk(deliveries: dict, light_ok: dict, start: int = 1) -> dict:
    """Upstream's rule over what the peers served.

    ``deliveries``: height -> [(peer, altered)] in serving order;
    ``light_ok``: height -> the verdict of the light check that the
    LastCommit of that height's altered block gets (the altered lane is
    in it). Returns the heights applied in order, the pairs refused as
    (height, first block's peer, second block's peer), the peers removed,
    and the heights whose stored seen commit came from an altered block.
    """
    used: dict = {}  # height -> deliveries refused so far
    applied, refused, removed, altered_seen = [], [], set(), set()

    def pick(h):
        got = deliveries.get(h, [])
        k = used.get(h, 0)
        return got[k] if k < len(got) else None

    h = start
    while True:
        first, second = pick(h), pick(h + 1)
        if first is None or second is None:
            break
        ok = not first[1] and (not second[1] or light_ok[h + 1])
        if ok:
            applied.append(h)
            if second[1]:
                altered_seen.add(h)
            h += 1
            continue
        refused.append((h, first[0], second[0]))
        removed.update((first[0], second[0]))
        used[h] = used.get(h, 0) + 1
        used[h + 1] = used.get(h + 1, 0) + 1
    return {"applied": applied, "refused": refused, "removed": removed,
            "altered_seen": altered_seen}
