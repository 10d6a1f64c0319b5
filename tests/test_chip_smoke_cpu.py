"""chip_smoke.py's main path composes on the CPU at tiny width.

The smoke's own deployment class and train function run here through
the same entry points (serve proxy -> replica -> DecodeScheduler ->
JaxSlotEngine; train.Trainer) against a tiny f32 config, with the flash
kernels under ``interpret=True`` — so its control flow is covered
before a chip call is spent on it. What only the chip can show (the
TPU platform, Mosaic custom calls) is ``check_on_chip``, not run here.
"""

import cloudpickle
import pytest

import chip_smoke
import ray_tpu

# workers unpickle the smoke's code by value: they need not import it
cloudpickle.register_pickle_by_value(chip_smoke)

TINY = dict(vocab=512, d_model=64, n_heads=4, n_layers=2, d_ff=128,
            max_seq=64, dtype="float32")


@pytest.fixture
def tpu_session():
    info = ray_tpu.init(num_cpus=4, num_tpus=1)
    yield info
    ray_tpu.shutdown()


def test_smoke_legs_compose_on_cpu(tpu_session, monkeypatch, tmp_path):
    # A TPU-lease worker selects the TPU platform and would (rightly)
    # raise on this machine; the composition test is about control
    # flow, so its workers run the lease on CPU jax (and keep their
    # CPU programs out of the checkout's compile cache).
    from ray_tpu._private import raylet as raylet_mod

    real_env = raylet_mod.Raylet._tpu_worker_env
    monkeypatch.setattr(
        raylet_mod.Raylet, "_tpu_worker_env",
        lambda self, chips: {**real_env(self, chips),
                             "JAX_PLATFORMS": "cpu",
                             "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})

    served = chip_smoke.serve_leg(
        vocab=TINY["vocab"], cfg_spec=TINY, lengths=(8, 16), max_len=64,
        tokens=(6, 12))
    assert served["token_counts"] == [12, 12, 12, 12, 6]
    assert served["device"]["chips"] == "0"
    assert served["decode"]["completed"] == 7  # 2 warm-up + 5
    assert served["decode"]["admitted_mid_batch"] > 0

    trained = chip_smoke.train_leg({
        "cfg": TINY, "batch": 2, "seq": 32, "steps": 5,
        "kernel_shapes": [(1, 32, 2, 16)],
        "decode_shapes": [(3, 4, 16, 16, 4, 256), (3, 8, 24, 16, 2, 256)],
        "kernel_dtype": "float32", "interpret": True})
    assert trained["device"]["chips"] == "0"
    assert trained["device"]["pid"] != served["device"]["pid"]
    assert len(trained["losses"]) == 5
    row, = trained["kernels"]
    errors = [row[k] for k in ("fwd_err", "dq_err", "dk_err", "dv_err")]
    # above 0: the kernels ran (interpreted), not attention() against
    # itself
    assert row["finite"] and 0 < min(errors) and max(errors) < 1e-4
    # the decode kernel ran (interpreted: chunks of 128 in 256 rows),
    # each slot up to its own position and not into the NaN past it
    for row in trained["decode_kernels"]:
        assert row["chunk"] == 128 and row["finite"]
        assert 0 < row["err"] < 1e-5


def test_on_chip_checks_reject_a_cpu_run():
    """The smoke cannot pass on a worker that is not on a bound TPU."""
    leg = {"leg": "serve", "prefill_mosaic_calls": {"128": 1},
           "device": {"platform": "cpu", "chips": "0"}}
    with pytest.raises(chip_smoke.SmokeFailure, match="not on a bound TPU"):
        chip_smoke.check_on_chip(leg, chip_smoke.BF16_TOL)
