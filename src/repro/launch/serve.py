"""Serving driver: greedy decode loop for any arch (reduced on CPU).

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --tokens 16

Demonstrates the full serve path end-to-end: parameter init on the
device, cache init, per-token decode_step, greedy sampling.  ``--full``
runs the published widths (one TPU chip holds llama3.2-1b in bf16); the
multi-model deadline scheduling layer above this lives in
repro.runtime.serve_runtime.
"""

from __future__ import annotations

import argparse
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.registry import ARCHS
from repro.launch.compile_cache import use_compile_cache
from repro.models.model_api import Model, build_model


class Served(NamedTuple):
    model: Model
    params: Any
    fed: jax.Array  # [batch, tokens] token fed at each step
    logits: jax.Array  # [batch, tokens, vocab] f32 decode logits per step
    first_s: float  # first step: compile + run
    step_s: float  # mean wall time of the later steps


def run(arch: str, tokens: int = 16, batch: int = 2, ctx: int = 64, reduced: bool = True,
        seed: int = 0) -> Served:
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced(dtype="float32")
    model = build_model(cfg)
    k_param, k_tok = jax.random.split(jax.random.PRNGKey(seed))
    params = jax.jit(model.init)(k_param)
    cache = model.init_cache(batch, ctx)
    step = jax.jit(model.decode_step)
    tok = jax.random.randint(k_tok, (batch,), 0, cfg.vocab_size, jnp.int32)
    fed, logits_all = [], []
    t0 = time.perf_counter()
    for i in range(tokens):
        fed.append(tok)
        logits, cache = step(params, tok, cache, jnp.int32(i))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits_all.append(logits)
        if i == 0:
            jax.block_until_ready(tok)
            t1 = time.perf_counter()
    jax.block_until_ready(tok)
    t2 = time.perf_counter()
    served = Served(model, params, jnp.stack(fed, axis=1), jnp.stack(logits_all, axis=1),
                    first_s=t1 - t0, step_s=(t2 - t1) / max(tokens - 1, 1))
    print(f"[serve] {arch} on {jax.devices()[0].device_kind}: {tokens} tokens x{batch}, "
          f"first step {served.first_s * 1e3:.0f} ms (compile incl.), then "
          f"{served.step_s * 1e3:.2f} ms/token")
    print(f"[serve] sample: {served.fed[0][:12].tolist()}")
    return served


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), required=True)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    use_compile_cache()
    run(args.arch, tokens=args.tokens, batch=args.batch, reduced=not args.full)


if __name__ == "__main__":
    main()
