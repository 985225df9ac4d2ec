"""``flash_tile_fill_pct`` (``benchmark/layer_metrics``): the reader on the
program's ``grid_plan`` at the training cell's shapes, and on a program
that has none. CPU only; nothing here is a device result."""
import importlib
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402

NAME = "flash_tile_fill_pct"
CELL = "train_dense_1chip"


def cell_run():
    _, config, traffic = harness.find_cell(harness.load_manifest(), CELL)
    return {"seq": traffic["seq"], "batch": 1, "config": config}


def test_manifest_entry():
    m = next(m for m in harness.load_manifest()["per_layer"]
             if m["name"] == NAME)
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "kernels",
                 "moves": "train_tok_s", "workloads": [CELL]}


def test_needed_pairs_over_pairs_inside_computed_tiles_at_the_cells_shapes():
    reader = importlib.import_module("benchmark.layer_metrics." + NAME)
    run = cell_run()
    plan = reader.plan_of(run)
    assert set(plan) == {"fwd", "dq", "dkv"}
    needed = 3 * 4096 * 4097 // 2
    computed = sum(k["compute_steps"] * k["tile"][0] * k["tile"][1]
                   for k in plan.values())
    assert harness.load_reader(NAME)(run) == pytest.approx(
        100.0 * needed / computed)
    assert 75.0 <= harness.load_reader(NAME)(run) < 100.0


@pytest.mark.parametrize("tile,fill", [(128, 96.99), (512, 88.91),
                                       (1024, 80.02)])
def test_the_price_of_a_tile(tile, fill):
    program = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    reader = importlib.import_module("benchmark.layer_metrics." + NAME)
    counts = program._plan_counts(tile, tile, 4096, 4096, True, 0, 0)
    assert reader.fill_pct({k: counts for k in ("fwd", "dq", "dkv")}) == \
        pytest.approx(fill, abs=0.01)


def test_a_run_or_a_program_with_nothing_to_read_reads_as_nothing(
        monkeypatch):
    read = harness.load_reader(NAME)
    assert read({}) is None                         # a serving run
    assert read({"seq": 4096}) is None
    program = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.delattr(program, "grid_plan")       # the parent's program
    assert read(cell_run()) is None
