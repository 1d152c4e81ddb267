"""Commit verification — the engine-wide hot path (types/validation.go).

All three façades tally voting power while streaming (pubkey, sign-bytes,
signature) triples into one device batch:

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
    ignore = lambda cs: cs.block_id_flag == BLOCK_ID_FLAG_ABSENT  # noqa: E731
    count = lambda cs: cs.block_id_flag == BLOCK_ID_FLAG_COMMIT  # noqa: E731
    _verify(
        chain_id, vals, commit, needed, ignore, count,
        count_all=True, by_index=True,
    )


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
    ignore = lambda cs: cs.block_id_flag != BLOCK_ID_FLAG_COMMIT  # noqa: E731
    count = lambda cs: True  # noqa: E731
    _verify(
        chain_id, vals, commit, needed, ignore, count,
        count_all=False, by_index=True,
    )


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
    ignore = lambda cs: cs.block_id_flag != BLOCK_ID_FLAG_COMMIT  # noqa: E731
    count = lambda cs: True  # noqa: E731
    _verify(
        chain_id, vals, commit, needed, ignore, count,
        count_all=False, by_index=False,
    )


def _verify(
    chain_id, vals, commit, needed, ignore, count, count_all, by_index
) -> None:
    from ..libs import devledger

    # ledger attribution default: an untagged commit verification is
    # the consensus apply path; outer tenants (the light service, the
    # blocksync reactor, statesync restores) declared first and win
    with devledger.caller_class("commit-verify"):
        if _should_batch_verify(vals, commit):
            _verify_batch(
                chain_id, vals, commit, needed, ignore, count, count_all,
                by_index,
            )
        else:
            _verify_single(
                chain_id, vals, commit, needed, ignore, count, count_all,
                by_index,
            )


def _verify_batch(
    chain_id, vals, commit, needed, ignore, count, count_all, by_index
) -> None:
    """Mirror of verifyCommitBatch (types/validation.go:153-257)."""
    bv = crypto_batch.create_commit_batch_verifier(vals)
    seen: dict[int, int] = {}
    batch_sig_idxs: list[int] = []
    tallied = 0
    # one span for the whole walk (sign-bytes, bv.add, tally), never one
    # per lane; bv.verify() has the verify.* phases of its own
    with libmetrics.light_phase("sign_bytes", "commit.sign_bytes") as ph:
        for idx, cs in enumerate(commit.signatures):
            if ignore(cs):
                continue
            if by_index:
                val = vals.validators[idx]
            else:
                val_idx, val = vals.get_by_address(cs.validator_address)
                if val is None:
                    continue
                if val_idx in seen:
                    raise VerificationError(
                        f"double vote from validator {val_idx} "
                        f"({seen[val_idx]} and {idx})"
                    )
                seen[val_idx] = idx
            sign_bytes = commit.vote_sign_bytes(chain_id, idx)
            bv.add(val.pub_key, sign_bytes, cs.signature)
            batch_sig_idxs.append(idx)
            if count(cs):
                tallied += val.voting_power
            if not count_all and tallied > needed:
                break
        ph.set(lanes=len(batch_sig_idxs))
    if tallied <= needed:
        raise NotEnoughVotingPowerError(got=tallied, needed=needed)
    ok, valid_sigs = bv.verify()
    if ok:
        return
    for i, sig_ok in enumerate(valid_sigs):
        if not sig_ok:
            idx = batch_sig_idxs[i]
            raise VerificationError(
                f"wrong signature (#{idx}): "
                f"{commit.signatures[idx].signature.hex()}"
            )
    raise VerificationError(
        "BUG: batch verification failed with no invalid signatures"
    )


def _verify_single(
    chain_id, vals, commit, needed, ignore, count, count_all, by_index
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
    seen: dict[int, int] = {}
    tallied = 0
    deferred: list[tuple] = []  # (idx, pubkey_data, sign_bytes, sig)
    stopped_early = False
    # Any raise inside the walk is HELD, not thrown: deferred ed25519
    # lanes collected earlier in the walk are still unverified, and the
    # unrouted walk raises at the earliest failing index — an invalid
    # deferred lane must surface before a later double-vote /
    # sign-bytes / wrong-signature error. All deferred lanes precede
    # the break point by construction, so resolving them first and
    # then re-raising preserves the reference error identity.
    walk_exc: BaseException | None = None
    for idx, cs in enumerate(commit.signatures):
        if ignore(cs):
            continue
        if by_index:
            val = vals.validators[idx]
        else:
            val_idx, val = vals.get_by_address(cs.validator_address)
            if val is None:
                continue
            if val_idx in seen:
                walk_exc = VerificationError(
                    f"double vote from validator {val_idx} "
                    f"({seen[val_idx]} and {idx})"
                )
                break
            seen[val_idx] = idx
        try:
            sign_bytes = commit.vote_sign_bytes(chain_id, idx)
        except Exception as e:
            walk_exc = e
            break
        if co is not None and coalesce.eligible(val.pub_key):
            deferred.append(
                (idx, val.pub_key, sign_bytes, cs.signature)
            )
        elif not val.pub_key.verify_signature(sign_bytes, cs.signature):
            walk_exc = VerificationError(f"wrong signature (#{idx})")
            break
        if count(cs):
            tallied += val.voting_power
        if not count_all and tallied > needed:
            stopped_early = True
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
    if stopped_early:
        return
    if tallied <= needed:
        raise NotEnoughVotingPowerError(got=tallied, needed=needed)
