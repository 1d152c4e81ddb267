"""Validator and ValidatorSet with proposer-priority rotation.

Reference: types/validator.go, types/validator_set.go:
* validators ordered by voting power desc, ties by address asc
  (ValidatorsByVotingPower, validator_set.go:752-767);
* IncrementProposerPriority: rescale to a 2*total window, shift by avg,
  then `times` rounds of (everyone += power; max -= total)
  (validator_set.go:116-178);
* set hash = merkle root of SimpleValidator proto encodings
  (validator.go:117-133), kept per set behind a check of what the leaves
  were made of (ValidatorSet.hash);
* GetByAddress answers from an address -> first index map, built on the
  first lookup and kept behind a check of the addresses it was built from
  (ValidatorSet.address_index);
* the set of its keys' types, which picks a commit check's batch backend
  (crypto/batch.create_commit_batch_verifier), kept the same way behind a
  check of the keys (ValidatorSet.key_types);
* updates: changed/added vals merged, added vals start at
  -1.125*new-total priority (validator_set.go:477-495).

Clipping arithmetic (safeAddClip/safeSubClip) saturates at int64 bounds.
"""

from __future__ import annotations

from dataclasses import dataclass

from ..crypto import merkle
from ..libs import metrics as libmetrics
from . import proto

INT64_MAX = (1 << 63) - 1
INT64_MIN = -(1 << 63)
MAX_TOTAL_VOTING_POWER = INT64_MAX // 8
PRIORITY_WINDOW_SIZE_FACTOR = 2


def _clip(v: int) -> int:
    return max(INT64_MIN, min(INT64_MAX, v))


def pubkey_proto_encode(pub_key) -> bytes:
    """tendermint.crypto.PublicKey oneof body (keys.proto: ed25519=1,
    secp256k1=2; sr25519=3 as Tendermint v0.35's keys.proto numbers it,
    so that a set of sr25519 validators, or a mixed one, has a hash)."""
    if pub_key.type == "ed25519":
        return proto.field_bytes(1, pub_key.bytes())
    if pub_key.type == "secp256k1":
        return proto.field_bytes(2, pub_key.bytes())
    if pub_key.type == "sr25519":
        return proto.field_bytes(3, pub_key.bytes())
    raise ValueError(f"unsupported key type {pub_key.type}")


@dataclass(slots=True)
class Validator:
    pub_key: object
    voting_power: int
    proposer_priority: int = 0
    address: bytes = b""

    def __post_init__(self) -> None:
        if not self.address:
            self.address = bytes(self.pub_key.address())

    def copy(self) -> "Validator":
        return Validator(
            pub_key=self.pub_key,
            voting_power=self.voting_power,
            proposer_priority=self.proposer_priority,
            address=self.address,
        )

    def bytes(self) -> bytes:
        """SimpleValidator proto encoding (validator.go:117-133)."""
        return proto.field_message(
            1, pubkey_proto_encode(self.pub_key)
        ) + proto.field_varint(2, self.voting_power)

    def compare_proposer_priority(self, other: "Validator") -> "Validator":
        if self.proposer_priority > other.proposer_priority:
            return self
        if self.proposer_priority < other.proposer_priority:
            return other
        if self.address < other.address:
            return self
        if self.address > other.address:
            return other
        raise ValueError("cannot compare identical validators")

    def validate_basic(self) -> None:
        if self.pub_key is None:
            raise ValueError("validator has nil pubkey")
        if self.voting_power < 0:
            raise ValueError("negative voting power")
        if len(self.address) != 20:
            raise ValueError("address must be 20 bytes")


def _sort_key(v: Validator):
    # power desc, then address asc.
    return (-v.voting_power, v.address)


class ValidatorSet:
    def __init__(self, validators: list[Validator]):
        self.validators: list[Validator] = sorted(
            (v.copy() for v in validators), key=_sort_key
        )
        self.proposer: Validator | None = None
        self._total: int | None = None
        # (root, pub_keys, voting_powers) of the last hash(); see hash()
        self._root_memo: tuple[bytes, tuple, tuple] | None = None
        # (addresses, address -> index) of the last address_index()
        self._addr_memo: tuple[list[bytes], dict[bytes, int]] | None = None
        # (pub_keys, key types) of the last key_types()
        self._types_memo: tuple[list, frozenset] | None = None
        if self.validators:
            self.increment_proposer_priority(1)

    # --- queries -------------------------------------------------------------

    def __len__(self) -> int:
        return len(self.validators)

    def is_nil_or_empty(self) -> bool:
        return not self.validators

    def total_voting_power(self) -> int:
        if self._total is None:
            total = sum(v.voting_power for v in self.validators)
            if total > MAX_TOTAL_VOTING_POWER:
                raise ValueError(
                    f"total voting power {total} exceeds max "
                    f"{MAX_TOTAL_VOTING_POWER}"
                )
            self._total = total
        return self._total

    def get_by_address(self, address: bytes) -> tuple[int, Validator | None]:
        """The first validator with ``address`` and its index, or
        (-1, None) (validator_set.go GetByAddress)."""
        idx = self.address_index().get(address, -1)
        return (idx, self.validators[idx]) if idx >= 0 else (-1, None)

    def address_index(self) -> dict[bytes, int]:
        """Each address's first index in the set; the caller only reads it.

        Built on the first call, never by construction, ``copy`` or
        ``update_with_change_set``. The set keeps it with a witness, as
        ``hash()`` keeps its root: the ordered addresses it was built
        from. Every call lists the present addresses and compares them with
        the witness (element by element, the same bytes objects compare by
        identity), so a change made in place (a validator replaced, added,
        removed or reordered, an address rewritten) is seen by the next
        call, which builds the map again. A caller that looks up many
        addresses at once (a commit walk) takes the map once, and a set of
        10,000 validators answers each lookup from a dict instead of a
        scan. ``copy()`` carries the memo, ``update_with_change_set`` drops
        it, and types/serialization never writes it.
        """
        addrs = [v.address for v in self.validators]
        memo = self._addr_memo
        if memo is not None and memo[0] == addrs:
            return memo[1]
        # reversed, so that the first index of a repeated address wins
        index = dict(zip(reversed(addrs), range(len(addrs) - 1, -1, -1)))
        self._addr_memo = (addrs, index)
        return index

    def key_types(self) -> frozenset:
        """The ``type`` of each validator's key, as a set (None for a key
        without one); the caller only reads it.

        Computed on the first call and kept with a witness, as
        ``address_index`` keeps its map: the ordered ``pub_key`` objects
        it was computed from. Every call compares them with the present
        keys (identity first; the key classes are frozen and compare by
        class and bytes), so a key replaced in place on a ``Validator``,
        or a validator added, removed or reordered, is seen by the next
        call, which scans the types again. A commit check asks twice
        (``crypto/batch.supports_commit_batch``, then the factory), and a
        light client's bisection checks one set many times a step: at
        10,000 keys the witness costs a fraction of the scan. One tuple
        published in one store; ``copy()`` carries it,
        ``update_with_change_set`` drops it, and types/serialization never
        writes it.
        """
        # a comprehension: the slot read is specialised, about twice the
        # speed of list(map(attrgetter("pub_key"), ...))
        pub_keys = [v.pub_key for v in self.validators]
        memo = self._types_memo
        reused = memo is not None and memo[0] == pub_keys
        if reused:
            types = memo[1]
        else:
            types = frozenset(
                [getattr(pk, "type", None) for pk in pub_keys]
            )
            self._types_memo = (pub_keys, types)
        libmetrics.observe_valset_key_types(reused)
        return types

    def get_by_index(self, index: int) -> Validator | None:
        if 0 <= index < len(self.validators):
            return self.validators[index]
        return None

    def has_address(self, address: bytes) -> bool:
        return self.get_by_address(address)[0] >= 0

    def hash(self) -> bytes:
        """Merkle root of the validators' SimpleValidator leaves, in order.

        A leaf is made of ``pub_key`` and ``voting_power`` alone (address
        and proposer priority are not hashed). The set keeps the root it
        last computed with a witness of those leaves: the ordered
        ``pub_key`` objects (referenced, so an identity is never reused;
        the key classes are frozen and compare by their bytes) and the
        ordered powers. The kept root is returned only while both still
        equal the set's present contents, so a change made in place
        (a power, a key, a validator replaced, added or removed) is seen
        by the next call, which then runs ``merkle.hash_from_byte_slices``
        as a set without a memo does. The memo is one tuple published in
        one store (two threads on a cold set both compute the same value);
        ``copy()`` carries it, ``update_with_change_set`` drops it, and
        types/serialization never writes it: it lives in this process only.
        """
        vals = self.validators
        with libmetrics.light_phase(
            "valset_hash", "types.valset_hash", validators=len(vals),
        ) as phase:
            keys = tuple([v.pub_key for v in vals])
            powers = tuple([v.voting_power for v in vals])
            memo = self._root_memo
            reused = (
                memo is not None and memo[1] == keys and memo[2] == powers
            )
            if reused:
                root = memo[0]
            else:
                root = merkle.hash_from_byte_slices([v.bytes() for v in vals])
                self._root_memo = (root, keys, powers)
            phase.set(reused=int(reused))
        libmetrics.observe_valset_hash(reused)
        return root

    def copy(self) -> "ValidatorSet":
        cp = ValidatorSet.__new__(ValidatorSet)
        cp.validators = [v.copy() for v in self.validators]
        cp.proposer = None
        cp._total = self._total
        # Validator.copy() keeps the pub_key and address objects: the
        # witnesses hold
        cp._root_memo = self._root_memo
        cp._addr_memo = self._addr_memo
        cp._types_memo = self._types_memo
        if self.proposer is not None:
            # one pass for one address: a lookup would build the copy's
            # address index, which copying never does
            addr = self.proposer.address
            cp.proposer = next(
                (v for v in cp.validators if v.address == addr), None
            )
            if cp.proposer is None:
                cp.proposer = self.proposer.copy()
        return cp

    # --- proposer rotation ---------------------------------------------------

    def increment_proposer_priority(self, times: int) -> None:
        if not self.validators:
            raise ValueError("empty validator set")
        if times <= 0:
            raise ValueError("times must be positive")
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self.rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        proposer = None
        for _ in range(times):
            proposer = self._increment_proposer_priority()
        self.proposer = proposer

    def copy_increment_proposer_priority(self, times: int) -> "ValidatorSet":
        cp = self.copy()
        cp.increment_proposer_priority(times)
        return cp

    def rescale_priorities(self, diff_max: int) -> None:
        if diff_max <= 0:
            return
        prios = [v.proposer_priority for v in self.validators]
        diff = abs(max(prios) - min(prios))
        if diff > diff_max:
            ratio = (diff + diff_max - 1) // diff_max
            for v in self.validators:
                # Go int64 division truncates toward zero.
                q = abs(v.proposer_priority) // ratio
                v.proposer_priority = q if v.proposer_priority >= 0 else -q

    def _shift_by_avg_proposer_priority(self) -> None:
        n = len(self.validators)
        total = sum(v.proposer_priority for v in self.validators)
        # Go big.Int Div floors (Euclidean for positive divisor).
        avg = total // n
        for v in self.validators:
            v.proposer_priority = _clip(v.proposer_priority - avg)

    def _increment_proposer_priority(self) -> Validator:
        for v in self.validators:
            v.proposer_priority = _clip(
                v.proposer_priority + v.voting_power
            )
        mostest = self.validators[0]
        for v in self.validators[1:]:
            mostest = mostest.compare_proposer_priority(v)
        mostest.proposer_priority = _clip(
            mostest.proposer_priority - self.total_voting_power()
        )
        return mostest

    def get_proposer(self) -> Validator:
        if self.proposer is None:
            self.proposer = self._find_proposer()
        return self.proposer

    def _find_proposer(self) -> Validator:
        mostest = self.validators[0]
        for v in self.validators[1:]:
            mostest = mostest.compare_proposer_priority(v)
        return mostest

    # --- updates -------------------------------------------------------------

    def update_with_change_set(self, changes: list[Validator]) -> None:
        """Apply ABCI validator updates (power 0 = removal).

        Reference semantics (validator_set.go:477-650): dedup/sort changes
        by address, verify removals exist, compute new total, added vals get
        priority -(new_total + new_total >> 3), then merge, re-sort by
        power, rescale + center priorities.
        """
        if not changes:
            return
        by_addr: dict[bytes, Validator] = {}
        for c in sorted(changes, key=lambda v: v.address):
            if c.address in by_addr:
                raise ValueError(f"duplicate update for {c.address.hex()}")
            if c.voting_power < 0:
                raise ValueError("negative voting power in update")
            by_addr[c.address] = c

        # the present validators by address: membership here, then the
        # merge (the address index is not built for an update)
        merged: dict[bytes, Validator] = {
            v.address: v for v in self.validators
        }
        removals = {a for a, c in by_addr.items() if c.voting_power == 0}
        for addr in removals:
            if addr not in merged:
                raise ValueError(
                    f"cannot remove unknown validator {addr.hex()}"
                )

        new_total = 0
        for v in self.validators:
            upd = by_addr.get(v.address)
            new_total += v.voting_power if upd is None else upd.voting_power
        for addr, c in by_addr.items():
            if addr not in merged:
                new_total += c.voting_power
        if new_total > MAX_TOTAL_VOTING_POWER:
            raise ValueError("updates exceed max total voting power")
        if new_total == 0:
            raise ValueError("updates would remove all validators")

        for addr, c in by_addr.items():
            if addr in removals:
                merged.pop(addr, None)
                continue
            existing = merged.get(addr)
            nv = c.copy()
            if existing is None:
                nv.proposer_priority = -(new_total + (new_total >> 3))
            else:
                nv.proposer_priority = existing.proposer_priority
            merged[addr] = nv

        self.validators = sorted(merged.values(), key=_sort_key)
        self._total = None
        self._root_memo = None
        self._addr_memo = None
        self._types_memo = None
        self.rescale_priorities(
            PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        )
        self._shift_by_avg_proposer_priority()
        self.proposer = None

    def validate_basic(self) -> None:
        if not self.validators:
            raise ValueError("empty validator set")
        for v in self.validators:
            v.validate_basic()
        if self.proposer is not None:
            self.proposer.validate_basic()

    # --- commit verification façades (validator_set.go:660-678) -------------

    def verify_commit(self, chain_id, block_id, height, commit):
        from . import validation

        validation.verify_commit(chain_id, self, block_id, height, commit)

    def verify_commit_light(self, chain_id, block_id, height, commit):
        from . import validation

        validation.verify_commit_light(
            chain_id, self, block_id, height, commit
        )

    def verify_commit_light_trusting(self, chain_id, commit, trust_level):
        from . import validation

        validation.verify_commit_light_trusting(
            chain_id, self, commit, trust_level
        )
