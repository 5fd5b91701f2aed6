"""The ingest mix end to end on the CPU, sound and with its timed path
broken underneath: the check must see each fault."""
import numpy as np
import pytest

from bench.tests.tiny import run

PLAIN = "nyx-dmd-512-eb1e-4.ingest-plain"
ENHANCED = "nyx-temperature-512-eb1e-3.ingest"


def _break_batches(monkeypatch, how):
    """Wrap the executor's batch read: the encoder then sees other data than
    the field, as a fault where the answer is produced would."""
    from repro.exec import executor

    orig = executor._read_batch

    def broken(source, ids, plan):
        b = orig(source, ids, plan)
        if how == "altered":
            b[0, 0, 0, :] += 10 * float(np.abs(b).max())
        else:  # half of the batch left out: its first half stands in for it
            h = max(1, len(ids) // 2)
            b[h:len(ids)] = b[:len(ids) - h]
        return b

    monkeypatch.setattr(executor, "_read_batch", broken)


def test_plain_ingest_is_correct(tiny_root):
    r = run(tiny_root, PLAIN, control=True)
    assert r["correct"], r["checks"]
    assert r["checks"]["over_bound_ulp"]["value"] <= 2.0
    assert set(r["metrics"]) == {"ingest_MBps", "ratio", "setup_s"}
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["control"]) == {"bf16"}
    assert not r["control"]["bf16"]["correct"], r["control"]


@pytest.mark.parametrize("fault", ["altered", "half_batch"])
def test_plain_ingest_fault_is_not_correct(tiny_root, monkeypatch, fault):
    _break_batches(monkeypatch, fault)
    r = run(tiny_root, PLAIN)
    assert not r["correct"], r["checks"]


def test_enhanced_ingest_is_correct_and_control_is_not(trained_root):
    r = run(trained_root, ENHANCED, control=True)
    assert r["correct"], r["checks"]
    assert r["checks"]["enh_gain_db"]["value"] > 1.0
    assert not any(c["correct"] for c in r["control"].values()), r["control"]
    enh = r["control"]["fp8_enhancer"]["checks"]["enh_err"]
    assert enh["value"] > enh["max"], r["control"]


def test_training_step_that_returns_its_state_is_not_correct(trained_root, monkeypatch):
    from bench import control
    from repro.core import trainer

    monkeypatch.setattr(trainer, "train_step", trainer.train_step)  # restored after
    control.plant_unchanged_step()
    r = run(trained_root, ENHANCED)
    assert not r["correct"], r["checks"]
    assert r["checks"]["enh_gain_db"]["value"] < 1.0


@pytest.mark.parametrize("fault", ["altered", "half_batch"])
def test_enhanced_ingest_altered_answer_is_not_correct(trained_root, monkeypatch, fault):
    _break_batches(monkeypatch, fault)
    r = run(trained_root, ENHANCED)
    assert not r["correct"], r["checks"]
