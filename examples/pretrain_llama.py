"""Llama pretraining with the full hybrid stack (BASELINE.json configs[4/5]).

Run (8 virtual CPU devices):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/pretrain_llama.py --dp 2 --mp 2 --sharding 2 --steps 10

On a TPU pod slice the same script runs per host (paddle.distributed.launch)
with the real device count; mesh axes and shardings are identical.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.distributed.fleet.elastic import TrainingSupervisor
from paddle_tpu.framework.functional import FunctionalModule
from paddle_tpu.models import LlamaForCausalLM, llama_tiny


def parse():
    p = argparse.ArgumentParser()
    p.add_argument("--dp", type=int, default=2)
    p.add_argument("--mp", type=int, default=2)
    p.add_argument("--sharding", type=int, default=2)
    p.add_argument("--sep", type=int, default=1)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--amp", action=argparse.BooleanOptionalAction,
                   default=True)
    p.add_argument("--ckpt_dir", default="/tmp/llama_pretrain_ckpt")
    return p.parse_args()


def main():
    args = parse()
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": args.dp, "mp_degree": args.mp,
        "sharding_degree": args.sharding, "sep_degree": args.sep,
        "pp_degree": 1,
    }
    fleet.init(is_collective=True, strategy=strategy)
    mesh = mesh_mod.get_mesh()
    print("mesh:", dict(mesh.shape))

    paddle.seed(0)
    cfg = llama_tiny(use_recompute=True,
                     context_parallel=args.sep > 1)
    model = LlamaForCausalLM(cfg)
    fm = FunctionalModule(model, training=True)
    specs = fm.param_specs(LlamaForCausalLM.sharding_rules(),
                           fsdp_axis="sharding", fsdp_size=args.sharding)
    p_sh = [NamedSharding(mesh, s) for s in specs]
    data_sh = NamedSharding(mesh, P(("dp", "sharding"), "sep"))

    p = [jax.device_put(a, s) for a, s in zip(fm.param_arrays(), p_sh)]
    m = [jax.device_put(jnp.zeros_like(a), s) for a, s in zip(p, p_sh)]
    v = [jax.device_put(jnp.zeros_like(a), s) for a, s in zip(p, p_sh)]
    lr, b1, b2, eps, wd = args.lr, 0.9, 0.999, 1e-8, 0.01
    amp = args.amp

    def train_step(p, m, v, key, ids, labels):
        def loss_fn(ps):
            cps = [a.astype(jnp.bfloat16) if amp and a.dtype == jnp.float32
                   else a for a in ps]       # AMP-O2: bf16 compute,
            (loss, _), _ = fm(cps, [], key, ids, labels=labels)
            return loss                      # fp32 master weights

        loss, grads = jax.value_and_grad(loss_fn)(p)
        new_p, new_m, new_v = [], [], []
        for pa, g, mm, vv in zip(p, grads, m, v):
            g = g.astype(pa.dtype)
            mm = b1 * mm + (1 - b1) * g
            vv = b2 * vv + (1 - b2) * g * g
            new_p.append(pa - lr * (mm / (jnp.sqrt(vv) + eps) + wd * pa))
            new_m.append(mm)
            new_v.append(vv)
        return loss, new_p, new_m, new_v

    step = jax.jit(train_step, donate_argnums=(0, 1, 2))

    def _save(ckpt, i, p, m, v):
        def host(arrs):
            return [paddle.to_tensor(np.asarray(jax.device_get(a)))
                    for a in arrs]
        ckpt.save(i, {"p": host(p), "m": host(m), "v": host(v)})

    def train(start_step, state, ckpt):
        nonlocal p, m, v
        if state is not None:
            # restore the FULL optimizer state — params AND Adam moments —
            # so restart resumes the exact trajectory (and never touches
            # arrays donated to a failed step call)
            p = [jax.device_put(jnp.asarray(t.numpy()), s)
                 for t, s in zip(state["p"], p_sh)]
            m = [jax.device_put(jnp.asarray(t.numpy()), s)
                 for t, s in zip(state["m"], p_sh)]
            v = [jax.device_put(jnp.asarray(t.numpy()), s)
                 for t, s in zip(state["v"], p_sh)]
        rng = np.random.default_rng(123 + start_step)  # deterministic skip
        t0 = time.time()
        loss = None
        for i in range(start_step, args.steps):
            ids_np = rng.integers(0, cfg.vocab_size,
                                  (args.batch, args.seq + 1))
            # causal-LM pretraining: labels are next-token-shifted ids
            ids = jax.device_put(jnp.asarray(ids_np[:, :-1], jnp.int32),
                                 data_sh)
            labels = jax.device_put(jnp.asarray(ids_np[:, 1:], jnp.int32),
                                    data_sh)
            key = fm.next_key()
            loss, p, m, v = step(p, m, v, key, ids, labels)
            if i % 5 == 0 or i == args.steps - 1:
                dt = (time.time() - t0) / max(i - start_step + 1, 1)
                tok = args.batch * args.seq / dt
                print(f"step {i} loss {float(loss):.4f} "
                      f"({tok:,.0f} tokens/s)")
            if (i + 1) % 10 == 0:
                _save(ckpt, i + 1, p, m, v)
        if loss is None:     # resumed at/after the final step: nothing to do
            _, state2 = ckpt.load()
            return None
        return float(loss)

    sup = TrainingSupervisor(args.ckpt_dir, max_restarts=2)
    final_loss = sup.run(train)
    print("done, final loss", final_loss)


if __name__ == "__main__":
    main()
