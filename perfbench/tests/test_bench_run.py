"""run.py end to end on the CPU: it refuses to run without a chip, and with
the chip check stubbed here (never in the harness) each driver runs a toy
cell to its result line. With the timed path broken underneath, the
comparison comes out false."""
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import tiny  # noqa: E402
from perfbench import bench, serving  # noqa: E402
from perfbench import run as R  # noqa: E402
from perfbench.drivers import train_steps  # noqa: E402

TOY_GAP_LIMIT = tiny.GAP_LIMIT


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    return env


@pytest.mark.parametrize("where", ["checkout", "bare"])
def test_refuses_without_a_chip(tmp_path, where):
    """No accelerator: a non-zero exit and no result line, in the checkout
    and in a directory holding only BENCHMARK.json and perfbench/."""
    cwd = ROOT
    if where == "bare":
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
        shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        cwd = tmp_path
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "phi4mini.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=_cpu_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no accelerator" in p.stderr


def _run(monkeypatch, capsys, driver, cell=None, seconds=2):
    import jax
    monkeypatch.setattr(bench, "load_cell",
                        lambda name: cell or tiny.cell(driver))
    monkeypatch.setattr(bench, "enable_compile_cache", lambda: "off")
    rc = R.main(["--workload", "tiny", "--seed", str(2 ** 33 + 3),
                 "--seconds", str(seconds), "--trace", "0"],
                require=lambda n: jax.devices()[:n])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("driver", ["open_loop", "backlog", "train_steps"])
def test_driver_runs_to_its_result_line(monkeypatch, capsys, driver):
    cell = tiny.cell(driver)
    if driver != "train_steps":
        cell["traffic"]["check"]["max_logit_gap"] = TOY_GAP_LIMIT
    out = _run(monkeypatch, capsys, driver, cell)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in cell["end_to_end"]}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert list(out)[-1] == "checks"
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


def test_train_window_counts_every_step_it_sent(monkeypatch, capsys):
    """The train window keeps steps in flight and closes only after every
    step it dispatched has completed: at the close none is still being
    computed, each counts, and the rate's time spans at least the
    window's seconds."""
    sent, ready = [], []
    build = train_steps.build

    def counted(cfg, s, traffic, devices):
        init, step, state_abs, batch_abs = build(cfg, s, traffic, devices)

        def step_counted(state, batch):
            state, m = step(state, batch)
            sent.append(m["loss"])
            return state, m
        return init, step_counted, state_abs, batch_abs
    close = R.Run.close_window

    def close_window(self):
        ready.append(all(x.is_ready() for x in sent))
        close(self)
    monkeypatch.setattr(train_steps, "build", counted)
    monkeypatch.setattr(R.Run, "close_window", close_window)
    out = _run(monkeypatch, capsys, "train_steps", seconds=2)
    assert ready == [True]
    assert len(sent) == out["attempted"] + train_steps.CHECKED
    t = tiny.cell("train_steps")["traffic"]
    rate = out["metrics"]["train_tok_s"]["value"]
    assert out["attempted"] * t["batch"] * t["seq"] / rate >= 2


def test_altered_token_is_caught(monkeypatch, capsys):
    """A token altered where the decode step produces it."""
    build = serving.build

    def broken(cell, seed, log=bench.log):
        eng, s, t = build(cell, seed)
        dec = eng._decode

        def decode(*a):
            greedy, logits, lens, pool = dec(*a)
            return (greedy + 1) % s["vocab"], logits, lens, pool
        eng._decode = decode
        return eng, s, t

    monkeypatch.setattr(serving, "build", broken)
    cell = tiny.cell("open_loop")
    cell["traffic"]["check"]["max_logit_gap"] = TOY_GAP_LIMIT
    out = _run(monkeypatch, capsys, "open_loop", cell)
    assert out["correct"] is False
    c = out["checks"]["widest_logit_gap"]
    assert c["value"] > c["limit"]


def _broken_step(monkeypatch, fault):
    build = train_steps.build

    def broken(cfg, s, traffic, devices):
        init, step, state_abs, batch_abs = build(cfg, s, traffic, devices)
        if fault == "unchanged":
            import jax
            from repro.models import transformer as T
            loss = jax.jit(lambda p, b: T.loss_fn(cfg, p, b))

            def faulty(state, batch):
                return state, {"loss": loss(state["params"], batch)}
        else:
            half = dict(traffic, batch=traffic["batch"] // 2)
            step_half = build(cfg, s, half, devices)[1]

            def faulty(state, batch):
                return step_half(state, {k: v[:half["batch"]]
                                         for k, v in batch.items()})
        return init, faulty, state_abs, batch_abs
    monkeypatch.setattr(train_steps, "build", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_training_fault_is_caught(monkeypatch, capsys, fault):
    """A step that returns its state unchanged, or that takes the mean over
    half of the batch, leaving the other half out."""
    _broken_step(monkeypatch, fault)
    out = _run(monkeypatch, capsys, "train_steps")
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
