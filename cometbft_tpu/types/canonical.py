"""Canonical sign-bytes encodings (consensus-critical, byte-exact).

Reference: types/canonical.go, proto/tendermint/types/canonical.proto,
types/vote.go:139-161 (VoteSignBytes / VoteExtensionSignBytes),
types/proposal.go:102-116 (ProposalSignBytes). All sign bytes are uvarint
length-delimited protobuf (protoio.MarshalDelimited).

Message types: prevote=1, precommit=2, proposal=32
(proto/tendermint/types/types.proto:17-23).
"""

from __future__ import annotations

from array import array

from ..crypto import host_batch
from . import proto

PREVOTE_TYPE = 1
PRECOMMIT_TYPE = 2
PROPOSAL_TYPE = 32


def is_vote_type(msg_type: int) -> bool:
    return msg_type in (PREVOTE_TYPE, PRECOMMIT_TYPE)


def canonical_part_set_header(total: int, hash_: bytes) -> bytes:
    return proto.field_varint(1, total) + proto.field_bytes(2, hash_)


def canonical_block_id(block_id) -> bytes:
    """CanonicalBlockID body; b'' when the block id is nil (field omitted).

    The nested part-set header is gogoproto nullable=false: always emitted.
    """
    if block_id is None or block_id.is_nil():
        return b""
    psh = block_id.part_set_header
    return proto.field_bytes(1, block_id.hash) + proto.field_message(
        2, canonical_part_set_header(psh.total, psh.hash), always=True
    )


# One consensus round encodes O(validators) CanonicalVotes that differ
# ONLY in the timestamp field: the constant prefix (type|height|round|
# block-id) and suffix (chain-id) are cached per round context so the
# batch-ingest hot path (types/vote_set.add_votes_batch) re-encodes just
# the timestamp. Tiny working set (a handful of contexts per height);
# cleared wholesale when it grows past the bound. Byte-equality with the
# uncached encoding is pinned by tests.
_SIGN_TEMPLATE_CACHE: dict = {}
_SIGN_TEMPLATE_BOUND = 64


def _vote_template(
    chain_id: str, msg_type: int, height: int, round_: int, block_id
) -> tuple[bytes, bytes]:
    """(prefix, suffix) of a CanonicalVote around its timestamp field."""
    bid_key = (
        None
        if block_id is None or block_id.is_nil()
        else (
            bytes(block_id.hash),
            block_id.part_set_header.total,
            bytes(block_id.part_set_header.hash),
        )
    )
    key = (chain_id, msg_type, height, round_, bid_key)
    tpl = _SIGN_TEMPLATE_CACHE.get(key)
    if tpl is None:
        cbid = canonical_block_id(block_id)
        tpl = (
            proto.field_varint(1, msg_type)
            + proto.field_sfixed64(2, height)
            + proto.field_sfixed64(3, round_)
            + proto.field_message(4, cbid),
            proto.field_string(6, chain_id),
        )
        if len(_SIGN_TEMPLATE_CACHE) >= _SIGN_TEMPLATE_BOUND:
            _SIGN_TEMPLATE_CACHE.clear()
        _SIGN_TEMPLATE_CACHE[key] = tpl
    return tpl


def vote_sign_bytes(
    chain_id: str,
    msg_type: int,
    height: int,
    round_: int,
    block_id,
    timestamp_ns: int,
) -> bytes:
    """CanonicalVote sign bytes (types/vote.go:139, canonical.proto:30-37)."""
    prefix, suffix = _vote_template(
        chain_id, msg_type, height, round_, block_id
    )
    body = (
        prefix
        + proto.field_message(5, proto.timestamp(timestamp_ns), always=True)
        + suffix
    )
    return proto.delimited(body)


def vote_sign_bytes_many(
    chain_id: str,
    msg_type: int,
    height: int,
    round_: int,
    block_id,
    timestamps_ns,
):
    """``[vote_sign_bytes(..., t) for t in timestamps_ns]``, byte for byte,
    encoded together: the votes of one commit differ in the timestamp
    alone, so the template is looked up once and the native engine
    (native/edbatch.cpp edb_vote_sign_bytes) writes every lane in one
    call that keeps the interpreter lock. The lanes come back as the
    engine wrote them, a ``host_batch.MsgColumn`` (one blob and its
    offsets): a ``list[bytes]`` to whoever indexes, iterates, slices or
    compares it, and never cut into lanes on the ed25519 device path,
    whose packer reads the blob in place.

    None where the lanes cannot be encoded together, and the caller
    encodes them one by one: no native engine on this machine, or a
    timestamp beyond int64 nanoseconds (Go's zero time, the timestamp of
    an absent CommitSig, is)."""
    if not timestamps_ns:
        return []
    prefix, suffix = _vote_template(
        chain_id, msg_type, height, round_, block_id
    )
    try:
        timestamps = array("q", timestamps_ns)
    except OverflowError:
        return None
    return host_batch.vote_sign_bytes(prefix, suffix, timestamps)


def proposal_sign_bytes(
    chain_id: str,
    height: int,
    round_: int,
    pol_round: int,
    block_id,
    timestamp_ns: int,
) -> bytes:
    """CanonicalProposal sign bytes (types/proposal.go:110)."""
    cbid = canonical_block_id(block_id)
    body = (
        proto.field_varint(1, PROPOSAL_TYPE)
        + proto.field_sfixed64(2, height)
        + proto.field_sfixed64(3, round_)
        + proto.field_varint(4, pol_round)
        + proto.field_message(5, cbid)
        + proto.field_message(6, proto.timestamp(timestamp_ns), always=True)
        + proto.field_string(7, chain_id)
    )
    return proto.delimited(body)


def vote_extension_sign_bytes(
    chain_id: str, height: int, round_: int, extension: bytes
) -> bytes:
    """CanonicalVoteExtension sign bytes (canonical.proto:41-46)."""
    body = (
        proto.field_bytes(1, extension)
        + proto.field_sfixed64(2, height)
        + proto.field_sfixed64(3, round_)
        + proto.field_string(4, chain_id)
    )
    return proto.delimited(body)
