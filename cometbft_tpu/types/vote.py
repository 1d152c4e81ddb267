"""Vote and Proposal (reference: types/vote.go, types/proposal.go).

A Vote is one validator's signed prevote/precommit for a block (or nil).
Sign bytes are the canonical length-delimited protobuf of CanonicalVote
(types/vote.go:139-161); extensions sign a separate CanonicalVoteExtension
(precommits for non-nil blocks only).
"""

from __future__ import annotations

from dataclasses import dataclass
from dataclasses import field as dc_field

from . import canonical
from .block import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    BlockID,
    CommitSig,
)

MAX_VOTE_EXTENSION_SIZE = 1024 * 1024  # types/params.go default cap


class VoteError(Exception):
    pass


@dataclass(slots=True)
class Vote:
    msg_type: int  # PREVOTE_TYPE | PRECOMMIT_TYPE
    height: int
    round: int
    block_id: BlockID  # nil BlockID = vote for nil
    timestamp_ns: int
    validator_address: bytes
    validator_index: int
    signature: bytes = b""
    extension: bytes = b""
    extension_signature: bytes = b""

    # ((chain_id, height, round, extension), sign bytes): the extension's
    # sign-bytes as last encoded, answered again only for the same four.
    # An in-memory cache like Commit._hash: never compared or serialized.
    _ext_sign_bytes: tuple | None = dc_field(
        default=None, compare=False, repr=False
    )

    def is_nil(self) -> bool:
        return self.block_id.is_nil()

    def sign_bytes(self, chain_id: str) -> bytes:
        return canonical.vote_sign_bytes(
            chain_id,
            self.msg_type,
            self.height,
            self.round,
            self.block_id,
            self.timestamp_ns,
        )

    def extension_sign_bytes(self, chain_id: str) -> bytes:
        """CanonicalVoteExtension sign bytes, encoded once a vote: the
        drain's pre-verification, the pre-app check and admission's memo
        key all ask for the same bytes of the same object."""
        key = (chain_id, self.height, self.round, self.extension)
        kept = self._ext_sign_bytes
        if kept is None or kept[0] != key:
            kept = self._ext_sign_bytes = (
                key, canonical.vote_extension_sign_bytes(*key)
            )
        return kept[1]

    def verify(self, chain_id: str, pub_key) -> None:
        """Signature + address check (types/vote.go:210-232).

        The signature check routes through the cross-caller verify
        coalescer when one is active (crypto/coalesce): identical
        verdicts — the coalescer runs the same kernels/host verifiers
        — but concurrent per-vote callers share one device launch.
        Unrouted (no coalescer, foreign key type, routing failure) it
        is exactly ``pub_key.verify_signature``.
        """
        from ..crypto import coalesce
        from ..libs import devledger

        if bytes(pub_key.address()) != self.validator_address:
            raise VoteError("invalid validator address")
        # ledger attribution default: an untagged vote verify is the
        # steady-state consensus path; outer tenants (the evidence
        # verifier, the light service) already declared and win
        with devledger.caller_class("consensus-vote"):
            ok = coalesce.verify_signature(
                pub_key, self.sign_bytes(chain_id), self.signature
            )
        if not ok:
            raise VoteError("invalid signature")

    def verify_vote_and_extension(self, chain_id: str, pub_key) -> None:
        """Verify vote + extension signature (types/vote.go:233-252)."""
        self.verify(chain_id, pub_key)
        if (
            self.msg_type == canonical.PRECOMMIT_TYPE
            and not self.block_id.is_nil()
        ):
            self.verify_extension(chain_id, pub_key)

    def verify_extension(self, chain_id: str, pub_key) -> None:
        """Extension signature only (types/vote.go:254-270); coalesced
        like :meth:`verify`."""
        from ..crypto import coalesce
        from ..libs import devledger

        if self.msg_type != canonical.PRECOMMIT_TYPE or self.block_id.is_nil():
            return
        with devledger.caller_class("consensus-vote"):
            ok = coalesce.verify_signature(
                pub_key, self.extension_sign_bytes(chain_id),
                self.extension_signature,
            )
        if not ok:
            raise VoteError("invalid extension signature")

    def commit_sig(self) -> CommitSig:
        """Convert to a commit slot (types/vote.go CommitSig)."""
        if self.block_id.is_complete():
            flag = BLOCK_ID_FLAG_COMMIT
        elif self.block_id.is_nil():
            flag = BLOCK_ID_FLAG_NIL
        else:
            raise VoteError(f"invalid block id {self.block_id} for conversion")
        return CommitSig(
            block_id_flag=flag,
            validator_address=self.validator_address,
            timestamp_ns=self.timestamp_ns,
            signature=self.signature,
        )

    def validate_basic(self) -> None:
        if self.msg_type not in (
            canonical.PREVOTE_TYPE,
            canonical.PRECOMMIT_TYPE,
        ):
            raise VoteError("invalid vote type")
        if self.height < 0:
            raise VoteError("negative height")
        if self.round < 0:
            raise VoteError("negative round")
        self.block_id.validate_basic()
        if not self.block_id.is_nil() and not self.block_id.is_complete():
            raise VoteError(f"block id must be nil or complete: {self.block_id}")
        if len(self.validator_address) != 20:
            raise VoteError("validator address must be 20 bytes")
        if self.validator_index < 0:
            raise VoteError("negative validator index")
        if not self.signature:
            raise VoteError("missing signature")
        if len(self.signature) > 64:
            raise VoteError("signature too long")
        if self.msg_type == canonical.PREVOTE_TYPE and (
            self.extension or self.extension_signature
        ):
            raise VoteError("prevotes cannot carry extensions")
        if self.is_nil() and (self.extension or self.extension_signature):
            # issue #8487: nil precommits must not carry extension data
            raise VoteError("nil votes cannot carry extensions")
        if len(self.extension) > MAX_VOTE_EXTENSION_SIZE:
            raise VoteError("extension too large")


def votes_sign_bytes(chain_id: str, votes) -> list[bytes]:
    """``[v.sign_bytes(chain_id) for v in votes]``, byte for byte. Votes
    that share type, height, round and block id differ in the timestamp
    alone, exactly like a commit's lanes, and are encoded together by the
    encoder those get (canonical.vote_sign_bytes_many); where it cannot
    take them, and for a vote alone in its group, vote by vote."""
    out: list = [None] * len(votes)
    groups: dict = {}
    for i, v in enumerate(votes):
        psh = v.block_id.part_set_header
        groups.setdefault(
            (v.msg_type, v.height, v.round, v.block_id.hash, psh.total,
             psh.hash), []
        ).append(i)
    for idxs in groups.values():
        first = votes[idxs[0]]
        encoded = None
        if len(idxs) > 1:
            encoded = canonical.vote_sign_bytes_many(
                chain_id, first.msg_type, first.height, first.round,
                first.block_id, [votes[i].timestamp_ns for i in idxs],
            )
        if encoded is None:
            encoded = [votes[i].sign_bytes(chain_id) for i in idxs]
        for i, sign_bytes in zip(idxs, encoded):
            out[i] = sign_bytes
    return out


@dataclass(slots=True)
class Proposal:
    """Block proposal (types/proposal.go)."""

    height: int
    round: int
    pol_round: int  # -1 if no proof-of-lock
    block_id: BlockID
    timestamp_ns: int
    signature: bytes = b""

    def sign_bytes(self, chain_id: str) -> bytes:
        return canonical.proposal_sign_bytes(
            chain_id,
            self.height,
            self.round,
            self.pol_round,
            self.block_id,
            self.timestamp_ns,
        )

    def validate_basic(self) -> None:
        if self.height < 0:
            raise VoteError("negative height")
        if self.round < 0:
            raise VoteError("negative round")
        if self.pol_round < -1 or self.pol_round >= self.round:
            raise VoteError("invalid pol round")
        self.block_id.validate_basic()
        if not self.block_id.is_complete():
            raise VoteError("proposal block id must be complete")
        if not self.signature or len(self.signature) > 64:
            raise VoteError("bad proposal signature")


__all__ = [
    "Vote",
    "votes_sign_bytes",
    "Proposal",
    "VoteError",
    "BLOCK_ID_FLAG_ABSENT",
    "BLOCK_ID_FLAG_COMMIT",
    "BLOCK_ID_FLAG_NIL",
]
