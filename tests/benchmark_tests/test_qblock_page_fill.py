"""``qblock_page_fill_pct`` (``benchmark/layer_metrics``): the reader on
hand-made spans in the program's tracer, and on what the kernel's entry
really records where a page is narrower than a lane row. CPU only; nothing
here is a device result."""
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

NAME = "qblock_page_fill_pct"


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


def window(tracer, *ts):
    return {"kernel_calls": [(tracer.origin + t, [1], [1]) for t in ts]}


@pytest.mark.parametrize("spans, want", [
    # a full wide step: 8 pages a step, every slot a page a pair needs
    ([dict(jobs=4, blocks=1, real_jobs=4, steps=4, pages_per_step=8,
           pages=32)], 100.0),
    # a part-full last job: 10 pages in two steps of 8
    ([dict(jobs=2, blocks=1, real_jobs=2, steps=2, pages_per_step=8,
           pages=10)], 62.5),
    # two calls sum their pages and slots; one page a step is full
    ([dict(jobs=3, blocks=1, real_jobs=3, steps=3, pages_per_step=8,
           pages=20),
      dict(jobs=8, blocks=1, real_jobs=8, steps=8, pages_per_step=1,
           pages=8)], 100.0 * 28 / 32),
], ids=["full", "part_full_last_job", "two_calls"])
def test_pages_needed_over_slots_fetched(tracer, spans, want):
    for i, args in enumerate(spans):
        record(tracer, 1.0 + i, **args)
    run = window(tracer, 0.5, 1.0 + len(spans))
    assert harness.load_reader(NAME)(run) == pytest.approx(want)


def test_latent_spans_outside_the_window_and_the_parents_are_left_out(
        tracer):
    record(tracer, 0.2, jobs=4, blocks=1, real_jobs=4, steps=4,
           pages_per_step=8, pages=1)                          # before
    record(tracer, 1.0, jobs=4, blocks=1, real_jobs=4, steps=4,
           pages_per_step=8, pages=24)
    record(tracer, 1.2, jobs=64, blocks=1, real_jobs=9, steps=64,
           latent=1)                                           # latent
    record(tracer, 1.4, jobs=256, blocks=32, real_jobs=200,
           steps=256)                                          # the parent's
    record(tracer, 9.0, jobs=4, blocks=1, real_jobs=4, steps=4,
           pages_per_step=8, pages=1)                          # after
    assert harness.load_reader(NAME)(window(tracer, 0.5, 2.0)) == \
        pytest.approx(75.0)


def test_a_program_whose_spans_lack_the_counts_reads_as_nothing(tracer):
    read = harness.load_reader(NAME)
    record(tracer, 1.0, jobs=256, blocks=32, real_jobs=200, steps=256)
    assert read(window(tracer, 0.5, 2.0)) is None
    assert read({"kernel_calls": []}) is None
    assert read({}) is None


def test_the_kernels_entry_records_what_the_reader_reads(tracer):
    """Pages of 16 tokens at head_dim 128: eight pages a step. Slot 0's
    context of 20 needs 2 pages (one step), slot 1's span reaches 28 in
    block 0 (2 pages) and 30 in block 1 (2 pages): 6 pages in 3 steps of
    8 slots; a bucket with a q-block of padding rows adds its one step."""
    import importlib
    import jax.numpy as jnp
    rpa = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    pool = jnp.zeros((2, 9, 16, 128), jnp.float32)
    assert rpa.pages_per_step(pool) == 8
    tables = np.arange(1, 9, dtype=np.int32).reshape(2, 4)
    t0 = tracer.origin
    run = {"kernel_calls": [(t0, [1], [1]), (t0 + 3600, [1], [1])]}
    for tokens, steps in ((16, 3), (24, 4)):
        rpa.ragged_paged_attention(
            jnp.zeros((tokens, 4, 128), jnp.float32), pool, pool, tables,
            np.array([0, 1]), np.array([0, 1]), np.array([1, 9]),
            np.array([20, 30]), interpret=True)
        args = tracer.completed()[-1].args
        assert (args["steps"], args["pages_per_step"], args["pages"]) == (
            steps, 8, 6)
    assert harness.load_reader(NAME)(run) == pytest.approx(
        100.0 * 12 / (8 * 7))


def test_manifest_entry():
    m = next(m for m in harness.load_manifest()["per_layer"]
             if m["name"] == NAME)
    assert "serve_chat_closed" in m["workloads"]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "kernels",
                 "moves": "serve_tok_s", "workloads": m["workloads"]}
