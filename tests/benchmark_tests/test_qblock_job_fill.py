"""``qblock_job_fill_pct`` (``benchmark/layer_metrics``): the reader on
hand-made spans in the program's tracer, and on what the kernel's entry
really records. CPU only; nothing here is a device result."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402
from paddle_tpu import profiler  # noqa: E402
from paddle_tpu.profiler import spans as spans_mod  # noqa: E402

NAME = "qblock_job_fill_pct"
CONFIG = {"num_key_value_heads": 8}


@pytest.fixture
def tracer():
    t = profiler.get_tracer()
    t.drain()
    t.enable()
    spans_mod.latch()
    yield t
    t.disable()
    t.drain()
    spans_mod.latch()


def record(tracer, t, **args):
    """One ``attn/qblock`` span that began ``t`` seconds after the
    tracer's origin."""
    with profiler.span("attn/qblock") as sp:
        sp.set(**args)
    tracer.completed()[-1].ts = t


def stamps(tracer, *ts):
    return [(tracer.origin + t, [1], [1]) for t in ts]


def test_manifest_entry():
    m = next(m for m in harness.load_manifest()["per_layer"]
             if m["name"] == NAME)
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "kernels",
                 "moves": "serve_tok_s", "workloads": ["serve_chat_closed"]}


def test_real_pairs_over_walked_pairs_whatever_the_heads_a_step(tracer):
    read = harness.load_reader(NAME)
    # a grid of one axis: 2,048 steps, every KV head in each
    record(tracer, 1.0, jobs=2048, blocks=32, real_jobs=1300, steps=2048)
    # a grid with a head axis: 8 x 1,024 steps of one head each
    record(tracer, 2.0, jobs=1024, blocks=4, real_jobs=600, steps=8192)
    run = {"kernel_calls": stamps(tracer, 0.5, 2.5), "config": CONFIG}
    assert read(run) == pytest.approx(100.0 * (1300 + 600) / (2048 + 1024))


def test_spans_outside_the_window_latent_or_without_counts_are_left_out(
        tracer):
    read = harness.load_reader(NAME)
    record(tracer, 0.2, jobs=64, blocks=1, real_jobs=1, steps=64)   # before
    record(tracer, 1.0, jobs=256, blocks=4, real_jobs=192, steps=256)
    record(tracer, 1.5, jobs=4096, blocks=8, real_jobs=9, steps=4096,
           latent=1)
    record(tracer, 1.7, jobs=256, blocks=32)        # the parent's span
    record(tracer, 9.0, jobs=64, blocks=1, real_jobs=1, steps=64)   # after
    run = {"kernel_calls": stamps(tracer, 0.5, 1.2, 2.0), "config": CONFIG}
    assert read(run) == pytest.approx(75.0)


def test_a_program_without_the_counts_reads_as_nothing(tracer):
    read = harness.load_reader(NAME)
    record(tracer, 1.0, jobs=256, blocks=32)
    assert read({"kernel_calls": stamps(tracer, 0.5, 2.0),
                 "config": CONFIG}) is None
    assert read({"kernel_calls": [], "config": CONFIG}) is None
    assert read({}) is None


def test_the_kernels_entry_records_what_the_reader_reads(tracer):
    import importlib
    import jax.numpy as jnp
    rpa = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    pool = jnp.zeros((2, 9, 8, 16), jnp.float32)
    tables = np.arange(1, 9, dtype=np.int32).reshape(2, 4)
    t0 = tracer.origin
    rpa.ragged_paged_attention(
        jnp.zeros((16, 4, 16), jnp.float32), pool, pool, tables,
        np.array([0, 1]), np.array([0, 1]), np.array([1, 9]),
        np.array([20, 30]), interpret=True)
    args = tracer.completed()[-1].args
    # block 0: slot 0's 3 pages + slot 1's pages to row 7 (context 28: 4);
    # block 1: slot 1's 4 pages
    assert args["real_jobs"] == 3 + 4 + 4 and args["blocks"] == 2
    assert args["jobs"] == args["steps"] == 11      # the list is the grid
    run = {"kernel_calls": [(t0, [1], [1]), (t0 + 3600, [1], [1])],
           "config": {"num_key_value_heads": 2}}
    assert harness.load_reader(NAME)(run) == pytest.approx(100.0)
    # a tick whose bucket holds a q-block of padding rows: its one job
    # has no owner
    rpa.ragged_paged_attention(
        jnp.zeros((24, 4, 16), jnp.float32), pool, pool, tables,
        np.array([0, 1]), np.array([0, 1]), np.array([1, 9]),
        np.array([20, 30]), interpret=True)
    assert tracer.completed()[-1].args["steps"] == 12
    assert harness.load_reader(NAME)(run) == pytest.approx(100.0 * 22 / 23)
