"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Brings up the continuous-batching engine (the MTC TRE payload) on the
reduced config and serves a synthetic request stream, reporting throughput
and slot utilization.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_smoke_config
from repro.configs.base import ParallelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.lm import LM
from repro.serve.engine import Engine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCHS))
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch)
    lm = LM(cfg)
    rt = lm.runtime(ParallelConfig(attn_q_chunk=16, attn_kv_chunk=16))
    params = jax.jit(lambda k: lm.init(k)[0])(jax.random.key(0))
    engine = Engine(lm, params, rt, max_batch=args.max_batch,
                    max_len=args.max_len)
    rng = np.random.default_rng(0)

    def make_req(i):
        shape = ((args.prompt_len,) if cfg.n_codebooks <= 1
                 else (args.prompt_len, cfg.n_codebooks))
        req = Request(rid=i, tokens=rng.integers(
            1, cfg.vocab_size, shape).astype(np.int32),
            max_new_tokens=args.new_tokens)
        if cfg.vision_stub:
            req.patches = rng.standard_normal(
                (cfg.n_patches, cfg.d_model)).astype(np.float32)
        return req

    reqs = [make_req(i) for i in range(args.requests)]
    t0 = time.time()
    done = engine.run(reqs)
    dt = time.time() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"arch={args.arch}: served {len(done)} requests, {toks} tokens in "
          f"{dt:.1f}s ({toks/dt:.1f} tok/s, {engine.steps} decode steps)")


if __name__ == "__main__":
    main()
