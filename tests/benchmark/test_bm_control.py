"""The comparison's control, kept at a size a test run can hold: the
reference computed in fp8, the nearest precision below the bfloat16 both
configurations state, comes out as not correct under each cell's own limits;
and the arithmetic of the comparison itself."""

import numpy as np
import pytest

import json
import sys
from pathlib import Path

from benchmark import compare, drivers, families, records, run, traffic
from benchmark.reference import train

CELLS = ["rn50-cached", "mistral7b-s8192", "mistral7b-s1024"]


def _batches(c, seed, n=3):
    mix, model = c["mix"], c["config"]["model"]
    records = traffic.make_records(mix, model, seed)
    b = mix["shape"]["batch"]
    rows = records[: n * b]
    return [{k: np.stack([r[k] for r in rows[i * b:(i + 1) * b]]) for k in rows[0]}
            for i in range(n)]


@pytest.mark.parametrize("cell", CELLS)
def test_fp8_control_is_not_correct_under_the_cells_limits(cell):
    c = run.load_cell(cell, rehearse=True)
    limits = run.load_cell(cell, rehearse=False)["cell"]["check"]["limits"]
    batches = _batches(c, seed=2 ** 31 + 5)
    ref = train.follow(c["config"], 2 ** 31 + 5, batches)
    again = train.follow(c["config"], 2 ** 31 + 5, batches)
    control = train.follow(c["config"], 2 ** 31 + 5, batches, mode="fp8")
    same = compare.numbers(again, ref)
    assert all(v == 0.0 for v in same.values())          # the reference repeats
    values = compare.numbers(control, ref)
    lim = {k: limits[k] for k in compare.NUMBERS if k in limits}
    ok, table = compare.verdict(values, lim)
    assert not ok, table
    half = compare.numbers(
        train.follow(c["config"], 2 ** 31 + 5, batches, fault="half_batch"), ref)
    assert not compare.verdict(half, lim)[0], half


def test_read_limits_puts_control_and_fault_through_the_verdict(tmp_path, monkeypatch):
    """The tool that reads a cell's limits on the chip, here at rehearsal
    size: each extra is judged by ``compare.verdict`` under the cell's own
    limits and recorded as not correct."""
    sys.path.insert(0, str(Path(__file__).parent))
    import read_limits

    out = tmp_path / "rows.jsonl"
    assert read_limits.main([
        "--workload", "mistral7b-s1024", "--seeds", f"{2 ** 31 + 9},4",
        "--seconds", "0.3", "--extra", "control,half_batch", "--extra-seeds", "1",
        "--out", str(out), "--rehearse"]) == 0
    rows = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert [r["seed"] for r in rows] == [2 ** 31 + 9, 4]
    assert all(r["program"]["correct"] is True for r in rows)
    for name in ("control", "half_batch"):
        assert rows[0][name]["correct"] is False
        table = rows[0][name]["compared"]
        assert any(v["limit"] is not None and v["value"] > v["limit"]
                   for v in table.values())
        assert name not in rows[1]          # read on the first seed alone


@pytest.mark.parametrize("load,what", [
    (families.load, "family"), (records.load, "record kind"),
    (drivers.load, "driver")])
def test_an_unknown_name_is_an_error(load, what):
    with pytest.raises(ValueError, match=what):
        load("no_such_thing")


def test_records_are_the_seeds_alone_and_every_seed_the_same_sizes():
    mix = {"records": {"kind": "image_f32", "count": 6, "shards": 2}}
    model = {"image_size": 16, "num_classes": 10}
    a, again = (traffic.make_records(mix, model, 2 ** 31 + 3) for _ in range(2))
    b = traffic.make_records(mix, model, 7)
    assert all(r["image"].dtype == np.float32 and r["image"].shape == (16, 16, 3)
               for r in a + b)
    assert all(np.array_equal(x["image"], y["image"]) for x, y in zip(a, again))
    assert not any(np.array_equal(x["image"], y["image"]) for x, y in zip(a, b))
    assert 0.6 < float(np.std(np.stack([r["image"] for r in a]))) < 0.8
    fed = [{"image": np.stack([a[0]["image"], b[0]["image"]])}]
    assert traffic.input_mismatches(mix, model, a, fed, 2 ** 31 + 3) == 1


def _reading(loss, grad, delta):
    return {"loss": list(loss), "grad_norm": dict(grad), "delta_norm": dict(delta)}


def test_numbers_by_the_worst_leaf_against_leaf_or_median():
    ref = _reading([2.0, 2.0, 2.0], {"a": 1.0, "b": 4.0, "c": 1e-9},
                   {"a": 0.1, "b": 0.2, "c": 5.0})
    prog = _reading([2.0, 2.02, 2.0], {"a": 1.1, "b": 4.0, "c": 0.5},
                    {"a": 0.1, "b": 0.1, "c": 0.0})
    n = compare.numbers(prog, ref)
    assert n["loss_gap"] == pytest.approx(0.01)
    # c's gradient is all but zero: its gap is held against the median leaf's norm
    assert n["grad_gap"] == pytest.approx(0.5 / 1.0)
    # c is under a thousandth of the median gradient: left out of the change
    assert n["delta_gap"] == pytest.approx(0.1 / 0.2)


def test_a_state_left_unchanged_reads_one():
    ref = _reading([2.0], {"a": 1.0, "b": 2.0}, {"a": 0.3, "b": 0.4})
    prog = _reading([2.0], {"a": 1.0, "b": 2.0}, {"a": 0.0, "b": 0.0})
    assert compare.numbers(prog, ref)["delta_gap"] == pytest.approx(1.0)
    moved_double = _reading([2.0], {"a": 1.0, "b": 2.0}, {"a": 0.6, "b": 0.8})
    assert compare.numbers(moved_double, ref)["delta_gap"] == pytest.approx(1.0)


def test_verdict_holds_each_number_to_its_own_limit():
    ok, table = compare.verdict({"loss_gap": 0.001, "grad_gap": 0.2},
                                {"loss_gap": 0.01, "grad_gap": 0.1})
    assert not ok and table["grad_gap"] == {"value": 0.2, "limit": 0.1}
    assert compare.verdict({"loss_gap": 0.0}, {"loss_gap": 0})[0]
    assert not compare.verdict({"loss_gap": float("nan")}, {"loss_gap": 1.0})[0]
    with pytest.raises(ValueError):
        compare.numbers(_reading([1.0], {"a": 1.0}, {"a": 1.0}),
                        _reading([1.0], {"b": 1.0}, {"b": 1.0}))
