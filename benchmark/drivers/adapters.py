"""From the benchmark's plain data to the program's types, and a provider
that serves them. This is the only place where generated inputs take the
program's shape; nothing here verifies anything."""

from __future__ import annotations

from cometbft_tpu.crypto.keys import Ed25519PubKey
from cometbft_tpu.light.errors import LightBlockNotFoundError
from cometbft_tpu.light.provider import Provider
from cometbft_tpu.types.block import (
    BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig, Header, PartSetHeader,
    Version,
)
from cometbft_tpu.types.light_block import LightBlock, SignedHeader
from cometbft_tpu.types.validator_set import Validator, ValidatorSet

from ..harness import chain as rawchain

PSH_TOTAL = 1


def validator_set(raw: rawchain.RawValidators) -> ValidatorSet:
    vals = ValidatorSet([
        Validator(Ed25519PubKey(pk), voting_power=rawchain.VOTING_POWER)
        for pk in raw.pubkeys
    ])
    got = [v.pub_key.data for v in vals.validators]
    if got != raw.pubkeys:
        raise RuntimeError(
            "the program orders this validator set otherwise than by "
            "ascending address"
        )
    return vals


class HeaderChain:
    """Headers of heights 1..n, hash-chained, one validator set throughout.
    Header fields other than the hashes are ``assumed`` constants."""

    def __init__(self, chain_id: str, n_heights: int, vals: ValidatorSet,
                 seed: int):
        self.chain_id = chain_id
        self.n_heights = n_heights
        self.vals = vals
        vh = vals.hash()
        fill = lambda tag: rawchain.seed_bytes(seed, "hdr", tag)  # noqa: E731
        psh_hash = fill("psh")
        self.headers: list[Header | None] = [None]
        self.block_ids: list[BlockID] = [BlockID()]
        for h in range(1, n_heights + 1):
            hdr = Header(
                version=Version(block=11, app=1),
                chain_id=chain_id,
                height=h,
                time_ns=rawchain.BASE_TIME_NS + h * rawchain.SECOND_NS,
                last_block_id=self.block_ids[h - 1],
                last_commit_hash=fill("lc"),
                data_hash=fill("data"),
                validators_hash=vh,
                next_validators_hash=vh,
                consensus_hash=fill("cons"),
                app_hash=fill("app"),
                last_results_hash=fill("res"),
                evidence_hash=fill("ev"),
                proposer_address=vals.validators[h % len(vals)].address,
            )
            self.headers.append(hdr)
            self.block_ids.append(BlockID(
                hash=hdr.hash(),
                part_set_header=PartSetHeader(total=PSH_TOTAL, hash=psh_hash),
            ))

    def block_tuple(self, height: int):
        bid = self.block_ids[height]
        return (height, bid.hash, bid.part_set_header.total,
                bid.part_set_header.hash)

    def now_ns(self) -> int:
        """A clock reading just past the chain's tip: inside every
        header's trusting period and never before a header's time."""
        return (rawchain.BASE_TIME_NS
                + (self.n_heights + 2) * rawchain.SECOND_NS)

    def light_block(self, raw: rawchain.RawCommit, addresses) -> LightBlock:
        """A fresh Commit object each time, as a provider that decodes a
        reply hands out: nothing memoised on it is carried between
        requests."""
        bid = self.block_ids[raw.height]
        commit = Commit(
            height=raw.height, round=raw.round, block_id=bid,
            signatures=[
                CommitSig(BLOCK_ID_FLAG_COMMIT, addr, ts, sig)
                for addr, ts, sig in zip(
                    addresses, raw.timestamps, raw.signatures
                )
            ],
        )
        return LightBlock(
            signed_header=SignedHeader(
                header=self.headers[raw.height], commit=commit
            ),
            validator_set=self.vals,
        )


class ChainProvider(Provider):
    """Serves light blocks from signed raw commits, each fetch a fresh
    object, as a provider that decodes a reply (or loads from a store)
    hands out."""

    def __init__(self, chain: HeaderChain, commits: dict, addresses):
        self._chain = chain
        self._commits = commits
        self._addresses = addresses
        self.fetches = 0

    def chain_id(self) -> str:
        return self._chain.chain_id

    def light_block(self, height: int) -> LightBlock:
        if height == 0:
            height = self._chain.n_heights
        raw = self._commits.get(height)
        if raw is None:
            raise LightBlockNotFoundError(height)
        return self.serve(raw)

    def serve(self, raw: rawchain.RawCommit) -> LightBlock:
        self.fetches += 1
        return self._chain.light_block(raw, self._addresses)

    def report_evidence(self, ev) -> None:
        pass
