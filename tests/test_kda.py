"""``ops/pallas/kda.py``: the one-token kernel and the chunked scan, both
forms (Pallas in interpret mode, plain XLA), against the token-by-token
recurrence: from zero and from a non-zero state, with ``g`` at both ends of
``(-5, 0)``, spans that do not end on a job's 16 rows, padding rows and
jobs, and the scratch slot."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas import kda

H, D, T, SLOTS = 4, 128, 70, 4
FORMS = [("xla", False), ("pallas", True)]
GATES = {"mid": lambda u: -5 * u, "near_zero": lambda u: -1e-3 * u,
         "near_minus_five": lambda u: -5 + 1e-3 * u}


def rows(gate, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(T, H, D)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(D)
    k = rng.normal(size=(T, H, D)).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(T, H, D)).astype(np.float32)
    g = GATES[gate](rng.uniform(size=(T, H, D))).astype(np.float32)
    beta = rng.uniform(size=(T, H)).astype(np.float32)
    return [jnp.asarray(a) for a in (q, k, v, g, beta)]


def state(zero, seed=1):
    s = np.random.default_rng(seed).normal(
        size=(SLOTS + 1, H, D, D)).astype(np.float32)
    return jnp.asarray(np.zeros_like(s) if zero else s)


def rel(a, b):
    return float(np.abs(np.asarray(a) - np.asarray(b)).max()
                 / max(np.abs(np.asarray(b)).max(), 1e-30))


def test_the_recurrence_is_the_equations():
    """One token by hand: S' = diag(e^g) S; S = S' + beta k (v - S'^T k)^T;
    o = S^T q."""
    q, k, v, g, beta = (np.asarray(a[:1]) for a in rows("mid"))
    s0 = np.asarray(state(False)[0])
    o, s = kda.kda_recurrence(*(jnp.asarray(a) for a in (q, k, v, g, beta)),
                              jnp.asarray(s0))
    for h in range(H):
        sp = np.exp(g[0, h])[:, None] * s0[h]
        want = sp + beta[0, h] * np.outer(k[0, h], v[0, h] - sp.T @ k[0, h])
        assert np.allclose(np.asarray(s)[h], want, atol=1e-5)
        assert np.allclose(np.asarray(o)[0, h], want.T @ q[0, h], atol=1e-5)


@pytest.mark.parametrize("impl,interpret", FORMS)
@pytest.mark.parametrize("gate", sorted(GATES))
@pytest.mark.parametrize("zero", [True, False])
def test_step_and_chunk_against_the_recurrence(impl, interpret, gate, zero):
    """A tick: two one-token rows (slots 1 and 3, two padding rows) and two
    spans of 38 and 29 tokens (slots 2 and 0; 3 + 2 jobs of 8 in the
    list): outputs and end states are the recurrence's, the rows of no span
    read zero, the scratch slot alone takes the padding."""
    q, k, v, g, beta = rows(gate)
    s0 = state(zero)
    starts, lens, slots = [3, 41], [38, 29], [2, 0]
    plan = kda.chunk_plan(T, starts, lens, slots, SLOTS, jobs=8)
    assert plan["jobs"] == 5 and plan["tokens"] == 67
    step_rows, step_slots = kda.step_rows(T, [0, 1], [1, 3], SLOTS, 4)
    o, s = kda.kda_step(q, k, v, g, beta, jnp.array(s0), step_rows,
                        step_slots, impl=impl, interpret=interpret)
    o, s = kda.kda_chunk(q, k, v, g, beta, s, plan, o, impl=impl,
                         interpret=interpret)
    for a, n, slot in list(zip(starts, lens, slots)) + [(0, 1, 1), (1, 1, 3)]:
        want_o, want_s = kda.kda_recurrence(
            q[a:a + n], k[a:a + n], v[a:a + n], g[a:a + n], beta[a:a + n],
            s0[slot])
        assert rel(o[a:a + n], want_o) < 1e-5
        assert rel(s[slot], want_s) < 1e-5
    assert not np.asarray(o[2]).any()             # in no span
    if zero:
        assert not np.asarray(s[SLOTS]).any()


@pytest.mark.parametrize("impl,interpret", FORMS)
def test_a_span_in_two_ticks_is_the_span_in_one(impl, interpret):
    """A chunk's end state is the next chunk's start: 50 tokens as 23 + 27
    (neither a multiple of a job's 16 rows) read what 50 at once read."""
    q, k, v, g, beta = rows("mid", seed=3)
    s = state(True)
    outs = []
    for a, n in ((0, 23), (23, 27)):
        plan = kda.chunk_plan(T, [a], [n], [1], SLOTS, jobs=4)
        o, s = kda.kda_chunk(q, k, v, g, beta, s, plan, impl=impl,
                             interpret=interpret)
        outs.append(np.asarray(o[a:a + n]))
    want_o, want_s = kda.kda_recurrence(q[:50], k[:50], v[:50], g[:50],
                                        beta[:50])
    assert rel(np.concatenate(outs), want_o) < 1e-5
    assert rel(s[1], want_s) < 1e-5


def test_the_plan_of_a_ticks_spans():
    plan = kda.chunk_plan(40, [0, 20], [17, 16], [5, 2], scratch=9, jobs=5)
    assert plan["jobs"] == 3 and plan["tokens"] == 33
    assert plan["meta"].tolist() == [[5, 5, 2, 9, 9], [1, 0, 1, 1, 1]]
    assert plan["pack"][:17].tolist() == list(range(17))
    assert plan["pack"][17:32].tolist() == [40] * 15      # the job's padding
    assert plan["pack"][32:48].tolist() == list(range(20, 36))
    assert plan["unpack"][16] == 16 and plan["unpack"][20] == 32
    assert plan["unpack"][17] == 5 * kda.SUB              # in no span
    assert kda.chunk_jobs_bound(512, 8) == 40
    with pytest.raises(ValueError):
        kda.chunk_plan(40, [0], [40], [0], scratch=9, jobs=2)
    with pytest.raises(ValueError):
        kda.step_rows(8, [0, 1, 2], [0, 1, 2], 9, pad_to=2)
