"""chip_smoke.py's phases at reduced width on the CPU: the serving phase
with the Pallas paged kernel in interpret mode (and with faults planted in
the kernel's inputs, which its decode gate must catch), the four-chip
trainer phase on 4 virtual devices, and the platform check that refuses the
CPU."""
import os
import subprocess
import sys

import pytest

from conftest import REPO, run_multidev

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


LENS = (20, 20, 40, 8, 40, 8, 16, 16)


def _serve_small(**kw):
    from repro.configs.base import get_config, reduced
    from repro.serving.engine import EngineConfig
    cfg = reduced(get_config("phi4-mini-3.8b"), layers=2, d_model=128,
                  vocab=512)
    ecfg = EngineConfig(block_size=8, num_blocks=64, max_blocks_per_seq=6,
                        max_slots=8, prefill_chunk=16, prefills_per_step=2,
                        attn_impl="kernel")
    return chip_smoke.serve_phase(cfg, ecfg, lens=LENS, shared=16,
                                  new_tokens=4, log=lambda s: None, **kw)


@pytest.fixture
def fresh_engine_steps():
    """The engine caches its jitted steps per config; a test that changes
    what they trace must not share them with the other tests."""
    from repro.serving.engine import engine
    engine._cached_step_fns.cache_clear()
    yield
    engine._cached_step_fns.cache_clear()


def test_serve_phase_matches_dense_path():
    res = _serve_small()
    assert res["requests"] == 8 and res["tokens"] == 32
    assert res["prefix_hit_tokens"] == 16      # prompt 1 reuses prompt 0's
    assert res["logit_max_abs_err"] <= chip_smoke.LOGIT_TOL
    assert res["decode_steps_checked"] == 8 * 3    # every decode step
    assert res["decode_logit_max_abs_err"] <= chip_smoke.LOGIT_TOL
    assert res["first_tokens_same"] == 8
    assert not res["decode_has_tpu_custom_call"]   # interpret mode off TPU


@pytest.mark.parametrize("fault", ["seq_len_short", "wrong_page"])
def test_serve_phase_catches_kernel_fault(monkeypatch, fresh_engine_steps,
                                          fault):
    """A paged kernel that attends one token too few, or reads a wrong page,
    passes the first-token check (prefill does not use the kernel) and
    must fail the decode-step check."""
    import jax.numpy as jnp
    import repro.kernels.paged_attention as PA
    inner = PA.paged_attention

    def faulty(q, k_pages, v_pages, tables, lens, **kw):
        if fault == "seq_len_short":
            lens = jnp.maximum(lens - 1, 0)
        else:
            tables = tables.at[:, 0].set(tables[:, 1])
        return inner(q, k_pages, v_pages, tables, lens, **kw)

    monkeypatch.setattr(PA, "paged_attention", faulty)
    with pytest.raises(chip_smoke.SmokeError,
                       match="decode-step logits differ") as err:
        _serve_small()
    print(f"{fault}: {err.value}")


def test_train_phase_on_four_devices():
    run_multidev(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import chip_smoke
        from repro.configs.base import get_config, reduced
        cfg = reduced(get_config('stablelm-3b'), layers=2, d_model=128,
                      vocab=512)
        res = chip_smoke.train_phase(cfg, steps=3, batch=8, seq=32,
                                     log=lambda s: None)
        assert res['step1_rel_diff'] <= chip_smoke.LOSS_RTOL, res
        print('PASS')
    """, devices=4)


def test_refuses_cpu():
    from repro.launch.mesh import cpu_devices_env
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env=cpu_devices_env(1), cwd=REPO)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr
    with pytest.raises(chip_smoke.SmokeError, match="no TPU"):
        chip_smoke.require_tpu()
