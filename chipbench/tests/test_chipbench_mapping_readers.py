"""The mapping layer's readers (``map_wait_ms_per_scene``,
``map_host_ms_per_scene``, ``scene_pad_row_share``) on made-up runs: one
value worked by hand each, and nothing read from a run with no scene
build or from a program without the spans and counters."""
from __future__ import annotations

import pytest

import chipbench_helpers as H  # noqa: F401  (import paths)
from chipbench import context, drive, system

READERS = ("map_wait_ms_per_scene", "map_host_ms_per_scene",
           "scene_pad_row_share")


def _run(phases: dict, tables0: dict, tables1: dict, completed: int = 4):
    w = drive.Window(t0=0.0, t1=2.0)
    for t in range(completed):
        w.results[t] = object()
    w.phases = phases
    w.stats0 = {"scene_tables": {"hits": 0, "misses": 0, **tables0}}
    w.stats1 = {"scene_tables": {"hits": 0, "misses": 8, **tables1}}
    return context.Run(config={}, ref=None, peak={}, window=w, setup_s=1.0)


def _read(run) -> dict:
    return {n: system.load_module("metrics", n).read(run) for n in READERS}


def test_mapping_readers_by_hand():
    # a fresh flush: 8 scenes of 11750..22250 voxels (136000 rows) built
    # at four 16384-row and four 32768-row scene rungs (196608 rows); a
    # streamed frame's delta merge adds its own wait
    run = _run({"map": [1000.0, 800.0], "delta_merge": [400.0],
                "scene_wait": [600.0, 500.0, 300.0]},
               {"rows": 1000, "rung_rows": 2048},
               {"rows": 1000 + 136000, "rung_rows": 2048 + 196608})
    read = _read(run)
    assert read["map_wait_ms_per_scene"] == pytest.approx(1400.0 / 4)
    assert read["map_host_ms_per_scene"] == pytest.approx(
        (1800.0 + 400.0 - 1400.0) / 4)
    assert read["scene_pad_row_share"] == pytest.approx(
        100 * (1 - 136000 / 196608))
    assert round(read["scene_pad_row_share"], 1) == 30.8


def test_wait_and_host_add_up_to_map_without_deltas():
    run = _run({"map": [1200.0, 900.0], "scene_wait": [700.0, 650.0]},
               {"rows": 0, "rung_rows": 0}, {"rows": 300, "rung_rows": 512})
    read = _read(run)
    assert (read["map_wait_ms_per_scene"] + read["map_host_ms_per_scene"]
            == pytest.approx(system.load_module(
                "metrics", "map_ms_per_scene").read(run)))


def test_nothing_read_without_scene_builds():
    # every slot hit the scene store: no wait, no rows
    run = _run({"map": [5.0, 7.0]}, {"rows": 500, "rung_rows": 1024},
               {"rows": 500, "rung_rows": 1024})
    assert _read(run) == dict.fromkeys(READERS)


def test_nothing_read_from_a_program_without_the_new_spans():
    # the engine before the mapping spans and counters: map phase only,
    # scene_tables without rows / rung_rows
    run = _run({"map": [1000.0, 800.0]}, {}, {})
    assert _read(run) == dict.fromkeys(READERS)
