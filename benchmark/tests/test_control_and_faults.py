"""Each cell at test size: a sound run is correct, the control comes out
not correct, and a fault planted in the timed path comes out not correct.

The control is the reference in the program's place with one stated
guarantee broken; the fault alters an answer where the program produces it.
"""

import pytest

from cellrun import correct_on_cpu, run_cell


@pytest.mark.parametrize("cell,seconds", [
    ("light10k-replay", 2.0), ("solo-kvstore-load", 4.0),
])
def test_sound_run_is_correct(cell, seconds):
    res = run_cell(cell, 2**31 + 12345, seconds)
    assert correct_on_cpu(res), res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("cell,control", [
    ("light10k-replay", "trust_all"), ("light10k-replay", "stride8"),
    ("solo-kvstore-load", "lose_acked"), ("solo-kvstore-load", "flat_hash"),
])
def test_control_is_not_correct(cell, control):
    res = run_cell(cell, 77, 3.0, control=control)
    assert not correct_on_cpu(res), res["checks"]


def test_verifier_that_answers_yes_is_caught(monkeypatch):
    """An answer altered where it is produced: the batch verifier says
    every lane verified."""
    from cometbft_tpu.crypto import batch as crypto_batch

    def yes(self):
        return True, [True] * len(self)

    monkeypatch.setattr(crypto_batch.Ed25519BatchVerifier, "verify", yes)
    res = run_cell("light10k-replay", 78, 3.0)
    assert not correct_on_cpu(res), res["checks"]
    assert res["checks"]["verdict_mismatches"]["value"] > 0


def test_wrong_data_hash_is_caught(monkeypatch):
    """An answer altered where it is produced: blocks carry a data hash
    that is not the RFC-6962 tree over the txs."""
    import hashlib

    from cometbft_tpu.types import block as types_block

    monkeypatch.setattr(
        types_block.Data, "hash",
        lambda self: hashlib.sha256(b"".join(self.txs)).digest(),
    )
    res = run_cell("solo-kvstore-load", 79, 4.0)
    assert not correct_on_cpu(res), res["checks"]
    assert res["checks"]["block_hash_mismatches"]["value"] > 0
