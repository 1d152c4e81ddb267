"""Light client tests (reference analog: light/verifier_test.go,
light/client_test.go, light/detector_test.go)."""

import dataclasses

import pytest

import helpers
from cometbft_tpu import light
from cometbft_tpu.light import detector as light_detector
from cometbft_tpu.light.errors import (
    ConflictingHeadersError,
    InvalidHeaderError,
    LightBlockNotFoundError,
    LightClientError,
    NewValSetCantBeTrustedError,
    OldHeaderExpiredError,
)
from cometbft_tpu.types.validation import Fraction

SECOND = 1_000_000_000
HOUR = 3600 * SECOND
PERIOD = 3 * HOUR
T0 = 1_700_000_000_000_000_000


def now_after(blocks, height):
    return blocks[height].time_ns + SECOND


class DictProvider(light.Provider):
    """In-memory provider over a prebuilt chain (provider/mock analog)."""

    def __init__(self, blocks, chain_id=helpers.CHAIN_ID):
        self.blocks = blocks
        self._chain_id = chain_id
        self.fetches = 0
        self.evidence = []

    def chain_id(self):
        return self._chain_id

    def light_block(self, height):
        self.fetches += 1
        if height == 0:
            height = max(self.blocks)
        if height not in self.blocks:
            raise LightBlockNotFoundError(height)
        return self.blocks[height]

    def report_evidence(self, ev):
        self.evidence.append(ev)


class TestVerifier:
    def test_adjacent_happy(self):
        blocks = helpers.make_light_chain(3)
        light.verify_adjacent(
            blocks[1].signed_header,
            blocks[2].signed_header,
            blocks[2].validator_set,
            PERIOD,
            now_after(blocks, 2),
        )

    def test_adjacent_rejects_wrong_next_vals(self):
        # independent chains: block 2's valset doesn't chain from block 1
        a = helpers.make_light_chain(3)
        b = helpers.make_light_chain(3, rotate=4)
        with pytest.raises((LightClientError, InvalidHeaderError)):
            light.verify_adjacent(
                a[1].signed_header,
                b[2].signed_header,
                b[2].validator_set,
                PERIOD,
                now_after(b, 2),
            )

    def test_adjacent_rejects_expired_trusted(self):
        blocks = helpers.make_light_chain(3)
        with pytest.raises(OldHeaderExpiredError):
            light.verify_adjacent(
                blocks[1].signed_header,
                blocks[2].signed_header,
                blocks[2].validator_set,
                PERIOD,
                blocks[1].time_ns + PERIOD + SECOND,
            )

    def test_adjacent_rejects_future_time(self):
        blocks = helpers.make_light_chain(3)
        with pytest.raises(InvalidHeaderError):
            light.verify_adjacent(
                blocks[1].signed_header,
                blocks[2].signed_header,
                blocks[2].validator_set,
                PERIOD,
                blocks[1].time_ns,  # "now" earlier than header 2's time
                max_clock_drift_ns=SECOND // 2,
            )

    def test_non_adjacent_happy_same_vals(self):
        blocks = helpers.make_light_chain(6)
        light.verify_non_adjacent(
            blocks[1].signed_header,
            blocks[1].validator_set,
            blocks[5].signed_header,
            blocks[5].validator_set,
            PERIOD,
            now_after(blocks, 5),
        )

    def test_non_adjacent_rejects_untrustable_val_set(self):
        # rotate all 4 validators every height: zero overlap at distance 2
        blocks = helpers.make_light_chain(6, rotate=4)
        with pytest.raises(NewValSetCantBeTrustedError):
            light.verify_non_adjacent(
                blocks[1].signed_header,
                blocks[1].validator_set,
                blocks[5].signed_header,
                blocks[5].validator_set,
                PERIOD,
                now_after(blocks, 5),
            )

    def test_non_adjacent_rejects_adjacent_headers(self):
        blocks = helpers.make_light_chain(3)
        with pytest.raises(LightClientError):
            light.verify_non_adjacent(
                blocks[1].signed_header,
                blocks[1].validator_set,
                blocks[2].signed_header,
                blocks[2].validator_set,
                PERIOD,
                now_after(blocks, 2),
            )

    def test_trust_level_bounds(self):
        light.validate_trust_level(Fraction(1, 3))
        light.validate_trust_level(Fraction(2, 3))
        light.validate_trust_level(Fraction(1, 1))
        for bad in (Fraction(1, 4), Fraction(4, 3), Fraction(0, 0)):
            with pytest.raises(LightClientError):
                light.validate_trust_level(bad)

    def test_verify_backwards(self):
        blocks = helpers.make_light_chain(3)
        light.verify_backwards(
            blocks[1].signed_header.header, blocks[2].signed_header.header
        )
        # non-chained headers fail
        other = helpers.make_light_chain(3, rotate=4)
        with pytest.raises(InvalidHeaderError):
            light.verify_backwards(
                other[1].signed_header.header, blocks[2].signed_header.header
            )


class TestStore:
    def test_save_load_prune(self):
        blocks = helpers.make_light_chain(5)
        store = light.Store()
        assert store.last_light_block_height() == -1
        assert store.first_light_block_height() == -1
        for h in (1, 3, 5):
            store.save_light_block(blocks[h])
        assert store.size() == 3
        assert store.first_light_block_height() == 1
        assert store.last_light_block_height() == 5
        assert store.light_block(3).height == 3
        assert store.light_block(3).hash() == blocks[3].hash()
        assert store.light_block_before(5).height == 3
        assert store.light_block_before(2).height == 1
        with pytest.raises(LightBlockNotFoundError):
            store.light_block(2)
        with pytest.raises(LightBlockNotFoundError):
            store.light_block_before(1)
        store.prune(1)
        assert store.size() == 1
        assert store.first_light_block_height() == 5
        store.delete_light_block(5)
        assert store.size() == 0

    def test_roundtrip_preserves_verifiability(self):
        """A store round trip must not break commit verification."""
        blocks = helpers.make_light_chain(3)
        store = light.Store()
        store.save_light_block(blocks[1])
        loaded = store.light_block(1)
        light.verify_adjacent(
            loaded.signed_header,
            blocks[2].signed_header,
            blocks[2].validator_set,
            PERIOD,
            now_after(blocks, 2),
        )


def make_client(blocks, witness_blocks=None, trust_height=1, **kw):
    primary = DictProvider(blocks)
    witnesses = (
        [DictProvider(witness_blocks)] if witness_blocks is not None else []
    )
    client = light.Client(
        chain_id=helpers.CHAIN_ID,
        trust_options=light.TrustOptions(
            period_ns=PERIOD,
            height=trust_height,
            hash=blocks[trust_height].hash(),
        ),
        primary=primary,
        witnesses=witnesses,
        **kw,
    )
    return client, primary


class TestClient:
    def test_sequential_adjacent(self):
        blocks = helpers.make_light_chain(4)
        client, _ = make_client(blocks)
        lb = client.verify_light_block_at_height(2, now_after(blocks, 2))
        assert lb.height == 2
        assert client.last_trusted_height() == 2

    def test_skipping_direct_jump_stable_vals(self):
        """No rotation: one non-adjacent check reaches the target."""
        blocks = helpers.make_light_chain(20)
        client, primary = make_client(blocks)
        fetch_before = primary.fetches
        lb = client.verify_light_block_at_height(20, now_after(blocks, 20))
        assert lb.height == 20
        # target fetch only — no intermediate pivots needed
        assert primary.fetches - fetch_before == 1
        assert [b.height for b in client.latest_trace] == [1, 20]

    def test_skipping_bisection_with_rotation(self):
        """Rotating 2 of 4 validators per height forces pivoting."""
        blocks = helpers.make_light_chain(20, rotate=2)
        client, primary = make_client(blocks)
        lb = client.verify_light_block_at_height(20, now_after(blocks, 20))
        assert lb.height == 20
        # trace must be a monotone verified chain ending at the target
        heights = [b.height for b in client.latest_trace]
        assert heights[0] == 1 and heights[-1] == 20
        assert heights == sorted(heights)
        assert len(heights) > 2  # really did bisect
        # every pivot is persisted
        for h in heights:
            assert client.trusted_store.light_block(h).height == h

    def test_bisection_attempts_and_trusting_checks_are_counted(self):
        """light_bisection_attempts_total{outcome} counts each attempt of
        the bisection once; the trusting phase times each skipping step's
        check of the trusted set, refused for trust or not."""
        from cometbft_tpu.libs import metrics as libmetrics

        m = libmetrics.NodeMetrics()
        libmetrics.push_node_metrics(m)
        try:
            blocks = helpers.make_light_chain(20, rotate=2)
            client, _ = make_client(blocks)
            client.verify_light_block_at_height(20, now_after(blocks, 20))
            heights = [b.height for b in client.latest_trace]
            tally = m.light_bisection_attempts_total.labels
            verified = tally("verified").value()
            cant_trust = tally("cant_trust").value()
            assert verified == len(heights) - 1
            assert cant_trust >= 1
            assert tally("refused").value() == 0
            skips = sum(1 for a, b in zip(heights, heights[1:]) if b > a + 1)
            trusting = m.light_verify_phase_seconds.labels("trusting")
            assert trusting._n == skips + cant_trust
            # every commit past the root with its first signature altered:
            # the first attempt that passes the trust level is refused
            forged = dict(blocks)
            for h in range(2, 21):
                commit = blocks[h].signed_header.commit
                sigs = list(commit.signatures)
                sig = sigs[0].signature
                sigs[0] = dataclasses.replace(
                    sigs[0], signature=bytes([sig[0] ^ 1]) + sig[1:])
                forged[h] = dataclasses.replace(
                    blocks[h],
                    signed_header=dataclasses.replace(
                        blocks[h].signed_header,
                        commit=dataclasses.replace(commit, signatures=sigs),
                    ),
                )
            client, _ = make_client(forged)
            with pytest.raises(Exception):
                client.verify_light_block_at_height(20, now_after(blocks, 20))
            assert tally("refused").value() == 1
        finally:
            libmetrics.pop_node_metrics(m)

    def test_backwards_verification(self):
        blocks = helpers.make_light_chain(10)
        client, _ = make_client(blocks, trust_height=8)
        lb = client.verify_light_block_at_height(3, now_after(blocks, 10))
        assert lb.height == 3
        assert client.first_trusted_height() == 3

    def test_rejects_wrong_trust_hash(self):
        blocks = helpers.make_light_chain(3)
        with pytest.raises(LightClientError):
            light.Client(
                chain_id=helpers.CHAIN_ID,
                trust_options=light.TrustOptions(
                    period_ns=PERIOD, height=1, hash=b"\x13" * 32
                ),
                primary=DictProvider(blocks),
            )

    def test_update_to_latest(self):
        blocks = helpers.make_light_chain(7)
        client, _ = make_client(blocks)
        lb = client.update(now_after(blocks, 7))
        assert lb is not None and lb.height == 7
        assert client.last_trusted_height() == 7

    def test_forged_target_rejected(self):
        """A primary serving a forged (unsigned-by-quorum) target fails."""
        blocks = helpers.make_light_chain(6)
        forged = dict(blocks)
        # graft block 6's header onto block 5's commit: hash mismatch
        forged[6] = dataclasses.replace(
            blocks[6],
            signed_header=dataclasses.replace(
                blocks[6].signed_header, commit=blocks[5].signed_header.commit
            ),
        )
        client, _ = make_client(forged)
        with pytest.raises(Exception):
            client.verify_light_block_at_height(6, now_after(blocks, 6))

    def test_cleanup_after(self):
        blocks = helpers.make_light_chain(6)
        client, _ = make_client(blocks)
        client.verify_light_block_at_height(6, now_after(blocks, 6))
        client.cleanup_after(1)
        assert client.last_trusted_height() == 1


class TestDetector:
    def test_agreeing_witness_no_evidence(self):
        blocks = helpers.make_light_chain(6)
        client, _ = make_client(blocks, witness_blocks=blocks)
        client.verify_light_block_at_height(6, now_after(blocks, 6))
        assert light_detector.detect_divergence(
            client, now_after(blocks, 6)
        ) == []

    def test_diverging_witness_raises_and_reports(self):
        """Witness with a validly-signed conflicting chain => attack
        evidence against the primary, reported to all providers."""
        # deterministic keys: the second call yields the same chain, with
        # header times shifted from the fork height on — a validly-signed
        # fork sharing the prefix (both chains 2/3-signed by the same set).
        primary_blocks = helpers.make_light_chain(8)
        witness_blocks = helpers.make_light_chain(
            8, fork_at=5, fork_delta_ns=500_000_000
        )
        assert primary_blocks[4].hash() == witness_blocks[4].hash()
        assert primary_blocks[8].hash() != witness_blocks[8].hash()
        client, primary = make_client(
            primary_blocks, witness_blocks=witness_blocks
        )
        client.verify_light_block_at_height(8, now_after(primary_blocks, 8))
        with pytest.raises(ConflictingHeadersError):
            light_detector.detect_divergence(
                client, now_after(primary_blocks, 8)
            )
        witness = client.witnesses[0]
        assert witness.evidence and primary.evidence
        ev = primary.evidence[0]
        assert ev.conflicting_block.hash() == primary_blocks[8].hash()
        assert ev.common_height in (1, 4)
        assert ev.byzantine_validators

