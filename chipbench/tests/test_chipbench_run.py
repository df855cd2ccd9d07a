"""A whole run of the harness on the CPU at a tiny size, the chip check
stubbed: data files found by name, and ``correct`` that fails for each
fault a served cell can have."""
from __future__ import annotations

import json

import numpy as np
import pytest

import chipbench_helpers as H
from chipbench import run

MIX = {"loop": "closed", "backlog": 4, "pool": 400, "streams": 0}
STREAMS = {"loop": "closed", "streams": 3, "churn_streams": 0.34,
           "churn_points": 0.1, "frames": 200,
           "serving": {"map_strategy": "incremental"}}
OPEN = {"loop": "open", "rate": 8.0, "streams": 0,
        "serving": {"flush_count": 2, "max_wait_ms": 100}}
ANSWERED = '"""Scenes answered in the window."""\n\n\ndef read(run):\n' \
           '    return float(run.window.completed)\n'


@pytest.fixture(scope="module", autouse=True)
def shared_compile_cache(tmp_path_factory):
    """One persistent compilation cache for this file's runs, so only the
    first of them compiles; the process's settings are restored after."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update(keys[1], 0)
    jax.config.update(keys[2], 0)
    cc.reset_cache()
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _cell(name, config, traffic):
    return {"name": name, "config": config, "traffic": traffic, "chips": 1,
            "why": "test"}


@pytest.fixture
def tree(tmp_path):
    cfg = H.tiny_config("minkunet-kitti-1x")
    cells = [_cell("tiny.backlog", "tiny-mink", "backlog"),
             _cell("tiny.streams", "tiny-mink", "streams"),
             _cell("tiny.open", "tiny-mink", "open"),
             _cell("tiny.only_here", "tiny-mink", "backlog")]
    return H.bench_tree(
        str(tmp_path), cells, {"tiny-mink": cfg},
        {"backlog": MIX, "streams": STREAMS, "open": OPEN},
        overrides={"tiny.only_here": {"backlog": 2}},
        metrics={"answered": ANSWERED})


def run_cell(monkeypatch, capsys, root, cell, seed=2 ** 31 + 17,
             seconds=0.5):
    monkeypatch.setattr(run, "device_or_exit", lambda chips: H.FakeDevice())
    monkeypatch.setattr(run, "compile_cache", lambda: None)
    with open(f"{root}/BENCHMARK.json") as f:
        bench = json.load(f)
    bench["end_to_end"].append({"name": "answered", "unit": "scenes",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock"})
    with open(f"{root}/BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    assert run.main(["--workload", cell, "--seed", str(seed),
                     "--seconds", str(seconds)], root=root) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["tiny.backlog", "tiny.streams",
                                  "tiny.open"])
def test_run_is_correct(tree, monkeypatch, capsys, cell):
    line = run_cell(monkeypatch, capsys, tree, cell)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 1}
    m = line["metrics"]
    assert m["setup_s"]["value"] > 0
    assert m["answered"]["value"] == line["attempted"]


def test_files_found_by_name(tree, monkeypatch, capsys):
    """A cell and a metric that exist only in this test's directory run;
    the cell's own file overrides its mix (a backlog of 2, not 4)."""
    line = run_cell(monkeypatch, capsys, tree, "tiny.only_here")
    assert line["correct"]
    assert line["metrics"]["answered"]["value"] % 2 == 0


def _scale_one_output(monkeypatch):
    from repro.core import plan
    apply = plan.NetworkPlan.apply

    def altered(self, *a, **k):
        return apply(self, *a, **k) * 1.01
    monkeypatch.setattr(plan.NetworkPlan, "apply", altered)


def _drop_second_scene(monkeypatch):
    from repro.serve import batcher
    unpack = batcher.SceneBatcher.unpack

    def half(*a, **k):
        out = unpack(*a, **k)
        return out[:1] + [out[0]] * (len(out) - 1)
    monkeypatch.setattr(batcher.SceneBatcher, "unpack", staticmethod(half))


def _swap_tickets(monkeypatch):
    from repro.serve.engine import Engine
    flush = Engine.flush

    def swapped(self):
        out = flush(self)
        keys = sorted(out)
        return dict(zip(keys, [out[k] for k in keys[1:] + keys[:1]]))
    monkeypatch.setattr(Engine, "flush", swapped)


def _ignore_deltas(monkeypatch):
    from repro.serve.engine import Engine

    def stale(self, stream, delta):
        return self.submit(self._streams[stream], stream=stream)
    monkeypatch.setattr(Engine, "submit_delta", stale)


def _refuse_largest(monkeypatch):
    from chipbench import drive
    from repro.serve.engine import Engine
    submit, warm_up = Engine.submit, drive.warm_up
    armed = []

    def refusing(self, scene, **k):
        if armed and len(scene.coords) > 300:
            raise ValueError("scene refused")
        return submit(self, scene, **k)

    def warm_then_arm(*a, **k):
        warm_up(*a, **k)
        armed.append(True)
    monkeypatch.setattr(Engine, "submit", refusing)
    monkeypatch.setattr(drive, "warm_up", warm_then_arm)


@pytest.mark.parametrize("cell,fault", [
    ("tiny.backlog", _scale_one_output),    # an answer altered where made
    ("tiny.backlog", _drop_second_scene),   # half of a batch left out
    ("tiny.backlog", _swap_tickets),        # answers handed to other tickets
    ("tiny.streams", _ignore_deltas),       # a stream's state left unchanged
    ("tiny.backlog", _refuse_largest),      # the largest scenes refused
])
def test_fault_is_not_correct(tree, monkeypatch, capsys, cell, fault):
    fault(monkeypatch)
    line = run_cell(monkeypatch, capsys, tree, cell)
    assert not line["correct"], line["checks"]
