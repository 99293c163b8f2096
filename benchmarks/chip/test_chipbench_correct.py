"""The comparison that decides ``correct``, run on the CPU at the smoke
widths: a sound run passes, and the control (the plain reference one
precision step down, int4 GEMMs, in the program's place) fails. A cell's
own limits are set from chip readings at its full size; at the smoke widths
the same numbers are held to limits set from smoke readings
(``testdata/<smoke config>.limits.json``). Everything but the look for a
chip is the harness's own run."""
from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import compare, runner, spec, traffic  # noqa: E402

# smoke widths of each cell's configuration (the program's test sizes)
SMOKE = {"dit-xl-512": "dit-smoke"}
SECONDS = 0.05      # one batch in the window, the rest of the bucket drained


def smoke_cell(workload: str, bucket: int = 2) -> spec.Cell:
    cell = spec.load_cell(workload)
    cfg = spec.load_json(HERE / "testdata" / f"{SMOKE[cell.config_name]}.json")
    cfg["serving"]["bucket"] = bucket
    smoke = spec.load_json(
        HERE / "testdata" / f"{SMOKE[cell.config_name]}.limits.json")
    limits = {"sample_batches": 1,
              "numbers": {k: smoke["numbers"][k]
                          for k in cell.limits["numbers"]}}
    return spec.Cell(name=cell.name, chips=cell.chips,
                     config_name=cell.config_name,
                     traffic_name=cell.traffic_name, config=cfg,
                     traffic=cell.traffic, limits=limits,
                     end_to_end=cell.end_to_end, per_layer=cell.per_layer)


def smoke_run(workload: str, seed: int, control: bool = False) -> dict:
    return runner.run_cell(smoke_cell(workload), seed, SECONDS, False,
                           time.monotonic(), control=control)


@pytest.mark.parametrize("workload", ["dit-xl-512.drift-mild-closed8",
                                      "dit-xl-512.clean-closed8"])
def test_sound_run_is_correct_and_the_control_is_not(workload):
    out = smoke_run(workload, 2 ** 31 + 5, control=True)
    limits = spec.readings(smoke_cell(workload).limits)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == set(limits)
    ok, checks = compare.decide(out["control"], limits)
    assert not ok, checks
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"images_per_s", "latency_p50_s",
                                   "latency_p90_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_an_open_loop_run_is_correct_with_partial_buckets():
    """A Poisson mix through the harness's own run: partial buckets are
    padded by the engine and compile nothing in the window, and what they
    served passes the comparison."""
    cell = smoke_cell("dit-xl-512.drift-mild-closed8")
    mix = {"arrival": "poisson", "rate_per_s": 3.0,
           "request": dict(cell.traffic["request"])}
    cell = dataclasses.replace(cell, traffic=mix)
    out = runner.run_cell(cell, 2 ** 31 + 12, 1.0, False, time.monotonic())
    assert traffic.arrival(cell.traffic) == "poisson"
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert all(m["value"] > 0 for m in out["metrics"].values())
