"""chip_smoke.py's serve-and-check flow on the CPU at a small size, and its
refusal to run anywhere but on a TPU."""
import chip_smoke
from repro.core.tidestore import DbConfig, KeyspaceConfig


def test_smoke_flow_serves_and_checks_on_cpu(tmp_path):
    cfg = DbConfig(keyspaces=[KeyspaceConfig("default", n_cells=16)])
    figures = chip_smoke.smoke(str(tmp_path), n_keys=4000, seed=1, cfg=cfg,
                               get_batch=512, get_batches=2,
                               exists_batch=2048, n_puts=64,
                               log=lambda *_: None)
    assert figures["bloom_dispatches"] == 1
    assert figures["bloom_rejected"] >= chip_smoke.MIN_BLOOM_NEGATIVE
    assert figures["kernel_lookups"] == 2 * 512
    assert figures["kernel_resolved"] >= chip_smoke.MIN_KERNEL_RESOLVED


def test_refuses_without_a_tpu(capsys, monkeypatch):
    # Whatever the host's backend, the smoke must stop before any work.
    monkeypatch.setattr(chip_smoke.jax, "default_backend", lambda: "cpu")
    monkeypatch.setattr(chip_smoke, "smoke", None)
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""
