"""Batched serving demo: prefill + lock-step decode with the serving loop.

    PYTHONPATH=src python examples/serve_lm.py --arch qwen3-14b
"""
import argparse

import numpy as np
import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models import build_model
from repro.runtime.serve_loop import ServeLoop, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args()

    cfg = get_config(args.arch).smoke()
    model = build_model(cfg, tp=1)
    params = model.init(jax.random.PRNGKey(0))
    loop = ServeLoop(model, params, max_seq=96)

    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        plen = int(rng.integers(4, 12))
        loop.submit(Request(rid, rng.integers(1, cfg.vocab_size, plen,
                                              dtype=np.int32),
                            max_new_tokens=args.max_new))
    done = loop.run()
    for r in done:
        print(f"request {r.rid}: prompt[{len(r.prompt)}] → {r.output}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
