"""The control at a size a test run holds: the plain reference computed in
fp8 (the precision below the served bf16) in the program's place must come
out as not correct, where the program itself passes.

The cells' limits were set from chip readings at their own sizes (PERF.md);
this is the same comparison on the toy decoder of ``tiny.py``, with the
toy's own limit between its readings."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import tiny  # noqa: E402
from perfbench import bench, control  # noqa: E402


def test_fp8_control_fails_where_the_program_passes(monkeypatch, capsys):
    import jax

    def cell(name):
        c = tiny.cell("open_loop")
        c["traffic"]["check"].update(tokens=300, requests=20,
                                     max_logit_gap=tiny.GAP_LIMIT)
        return c
    monkeypatch.setattr(bench, "load_cell", cell)
    monkeypatch.setattr(bench, "enable_compile_cache", lambda: "off")
    control.main(["toy", "--seconds", "3", "--seeds", "1", "2", "3"],
                 require=lambda n: jax.devices()[:n])
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert len(rows) == 3
    for r in rows:
        assert r["tokens_checked"] >= 150
        assert r["widest_logit_gap"] <= tiny.GAP_LIMIT
        assert r["control_widest_logit_gap"] > tiny.GAP_LIMIT
