"""Commit verification — the engine-wide hot path (types/validation.go).

All three façades select their lanes and tally voting power in one walk
(_select_lanes), encode the selected lanes' sign-bytes together and hand
the (pubkey, sign-bytes, signature) triples to one device batch:

* verify_commit          — full check, every signature (consensus apply path)
* verify_commit_light    — stop at +2/3, commit-flag sigs only (light/blocksync)
* verify_commit_light_trusting — trust-level fraction over a *different*
  validator set, lookup by address (light-client bisection)

Semantics follow types/validation.go:26-257 exactly, including the
batch-vs-single fallback threshold and the find-first-invalid error.
"""

from __future__ import annotations

from dataclasses import dataclass

from ..crypto import batch as crypto_batch
from ..libs import metrics as libmetrics
from .block import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BlockID,
    Commit,
)
from .validator_set import ValidatorSet

BATCH_VERIFY_THRESHOLD = 2  # types/validation.go:13-17


class VerificationError(Exception):
    pass


@dataclass
class NotEnoughVotingPowerError(VerificationError):
    got: int
    needed: int

    def __str__(self) -> str:
        return (
            f"invalid commit -- insufficient voting power: got {self.got}, "
            f"needed more than {self.needed}"
        )


@dataclass(frozen=True)
class Fraction:
    numerator: int
    denominator: int


DEFAULT_TRUST_LEVEL = Fraction(1, 3)


def _should_batch_verify(vals: ValidatorSet, commit: Commit) -> bool:
    # Unlike the reference (which keys off one type and bails to single
    # verifies when a mixed set trips Add, types/validation.go:170-176),
    # a heterogeneous set batches too: every key type just needs a
    # backend (crypto_batch.MixedBatchVerifier — one device launch).
    return (
        len(commit.signatures) >= BATCH_VERIFY_THRESHOLD
        and crypto_batch.supports_commit_batch(vals)
    )


def _verify_basic(vals, commit, height, block_id) -> None:
    if vals is None:
        raise VerificationError("nil validator set")
    if commit is None:
        raise VerificationError("nil commit")
    if len(vals) != len(commit.signatures):
        raise VerificationError(
            f"validator set size {len(vals)} != commit size "
            f"{len(commit.signatures)}"
        )
    if height != commit.height:
        raise VerificationError(
            f"invalid commit height {commit.height}, expected {height}"
        )
    if block_id != commit.block_id:
        raise VerificationError(
            f"invalid commit block id {commit.block_id}, expected {block_id}"
        )


def verify_commit(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
) -> None:
    """+2/3 check over ALL signatures (incl. nil votes) — consensus path."""
    _verify_basic(vals, commit, height, block_id)
    needed = vals.total_voting_power() * 2 // 3
    _verify(chain_id, vals, commit, needed, count_all=True, by_index=True)


def verify_commit_light(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
) -> None:
    """+2/3 check, commit-flag signatures only, stops when reached."""
    _verify_basic(vals, commit, height, block_id)
    needed = vals.total_voting_power() * 2 // 3
    _verify(chain_id, vals, commit, needed, count_all=False, by_index=True)


def verify_commit_light_trusting(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
) -> None:
    """trust-level fraction of a (possibly different) validator set."""
    if vals is None:
        raise VerificationError("nil validator set")
    if commit is None:
        raise VerificationError("nil commit")
    if trust_level.denominator == 0:
        raise VerificationError("trust level has zero denominator")
    needed = (
        vals.total_voting_power() * trust_level.numerator
    ) // trust_level.denominator
    _verify(chain_id, vals, commit, needed, count_all=False, by_index=False)


def _verify(chain_id, vals, commit, needed, count_all, by_index) -> None:
    from ..libs import devledger

    # ledger attribution default: an untagged commit verification is
    # the consensus apply path; outer tenants (the light service, the
    # blocksync reactor, statesync restores) declared first and win
    with devledger.caller_class("commit-verify"):
        if _should_batch_verify(vals, commit):
            _verify_batch(chain_id, vals, commit, needed, count_all, by_index)
        else:
            _verify_single(chain_id, vals, commit, needed, count_all, by_index)


def _select_lanes(vals, commit, needed, count_all, by_index):
    """The lanes a commit check verifies, in commit order, and their tally:
    ``(idxs, validators, tallied, double_vote)``.

    ``count_all`` (verify_commit) takes every signature that is not
    absent, nil votes too, and counts the power of those for the block;
    without it only signatures for the block are taken and the walk
    stops at the first lane where the tally passes ``needed``.
    ``by_index`` reads the validator at the signature's index; without
    it the validator is looked up by address, unknown addresses are
    skipped, and a validator's second signature ends the walk:
    ``double_vote`` is then that error, held for the caller, which may
    owe an earlier lane its own.
    """
    idxs: list[int] = []
    lane_vals: list = []
    seen: dict[int, int] = {}
    tallied = 0
    validators = vals.validators
    # one address index for the whole walk: a lookup per signature
    index = None if by_index else vals.address_index()
    for idx, cs in enumerate(commit.signatures):
        flag = cs.block_id_flag
        if flag != BLOCK_ID_FLAG_COMMIT and (
            not count_all or flag == BLOCK_ID_FLAG_ABSENT
        ):
            continue
        if by_index:
            val = validators[idx]
        else:
            val_idx = index.get(cs.validator_address, -1)
            if val_idx < 0:
                continue
            val = validators[val_idx]
            if val_idx in seen:
                return idxs, lane_vals, tallied, VerificationError(
                    f"double vote from validator {val_idx} "
                    f"({seen[val_idx]} and {idx})"
                )
            seen[val_idx] = idx
        idxs.append(idx)
        lane_vals.append(val)
        if flag == BLOCK_ID_FLAG_COMMIT:
            tallied += val.voting_power
        if not count_all and tallied > needed:
            break
    return idxs, lane_vals, tallied, None


def _verify_batch(
    chain_id, vals, commit, needed, count_all, by_index
) -> None:
    """Mirror of verifyCommitBatch (types/validation.go:153-257)."""
    bv = crypto_batch.create_commit_batch_verifier(vals)
    # one span for the whole walk (selection, one encoding of the
    # selected lanes' sign-bytes, one bv.add_many), never one per lane;
    # bv.verify() has the verify.* phases of its own
    with libmetrics.light_phase("sign_bytes", "commit.sign_bytes") as ph:
        idxs, lane_vals, tallied, double_vote = _select_lanes(
            vals, commit, needed, count_all, by_index
        )
        if double_vote is not None:
            raise double_vote
        encoder = "batched"
        sign_bytes = commit.vote_sign_bytes_many(chain_id, idxs)
        if sign_bytes is None:
            # no native engine on this machine, or a timestamp beyond
            # int64 nanoseconds: the per-vote encoder, lane by lane
            encoder = "per_lane"
            sign_bytes = [
                commit.vote_sign_bytes(chain_id, idx) for idx in idxs
            ]
        signatures = commit.signatures
        bv.add_many(
            [val.pub_key for val in lane_vals],
            sign_bytes,
            [signatures[idx].signature for idx in idxs],
        )
        libmetrics.observe_commit_sign_bytes(encoder, len(idxs))
        ph.set(lanes=len(idxs), encoder=encoder)
    if tallied <= needed:
        raise NotEnoughVotingPowerError(got=tallied, needed=needed)
    ok, valid_sigs = bv.verify()
    if ok:
        return
    for i, sig_ok in enumerate(valid_sigs):
        if not sig_ok:
            idx = idxs[i]
            raise VerificationError(
                f"wrong signature (#{idx}): "
                f"{commit.signatures[idx].signature.hex()}"
            )
    raise VerificationError(
        "BUG: batch verification failed with no invalid signatures"
    )


def _verify_single(
    chain_id, vals, commit, needed, count_all, by_index
) -> None:
    """Mirror of verifyCommitSingle (types/validation.go:266-330).

    With a cross-caller coalescer routed (crypto/coalesce), the
    eligible per-signature verifies of one commit are deferred and
    submitted as a group — concurrent single-verify commit checks
    (light bisection, evidence) then share device micro-batches — with
    the same tally walk, the same early stop, and the same
    first-invalid error by index. Ineligible key types verify inline
    exactly as before.
    """
    from ..crypto import coalesce

    co = coalesce.active()
    deferred: list[tuple] = []  # (idx, pubkey_data, sign_bytes, sig)
    # Any error of the walk is HELD, not thrown: deferred ed25519
    # lanes collected earlier in the walk are still unverified, and the
    # unrouted walk raises at the earliest failing index — an invalid
    # deferred lane must surface before a later double-vote /
    # sign-bytes / wrong-signature error. All deferred lanes precede
    # the break point by construction, so resolving them first and
    # then re-raising preserves the reference error identity.
    idxs, lane_vals, tallied, walk_exc = _select_lanes(
        vals, commit, needed, count_all, by_index
    )
    libmetrics.observe_commit_sign_bytes("per_lane", len(idxs))
    for idx, val in zip(idxs, lane_vals):
        try:
            sign_bytes = commit.vote_sign_bytes(chain_id, idx)
        except Exception as e:
            walk_exc = e
            break
        signature = commit.signatures[idx].signature
        if co is not None and coalesce.eligible(val.pub_key):
            deferred.append((idx, val.pub_key, sign_bytes, signature))
        elif not val.pub_key.verify_signature(sign_bytes, signature):
            walk_exc = VerificationError(f"wrong signature (#{idx})")
            break
    if deferred:
        bits = coalesce.verify_bytes(
            [pk.data for _, pk, _, _ in deferred],
            [sb for _, _, sb, _ in deferred],
            [sig for _, _, _, sig in deferred],
        )
        if bits is None:  # coalescer went away mid-walk: host verify
            bits = [
                pk.verify_signature(sb, sig)
                for _, pk, sb, sig in deferred
            ]
        for (idx, _, _, _), ok in zip(deferred, bits):
            if not ok:
                raise VerificationError(f"wrong signature (#{idx})")
    if walk_exc is not None:
        raise walk_exc
    if tallied <= needed:
        raise NotEnoughVotingPowerError(got=tallied, needed=needed)
