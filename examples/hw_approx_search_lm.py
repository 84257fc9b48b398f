"""The paper's Eq. (3) applied to an LM: NSGA-II over per-tensor weight
formats (bf16 / int8 / pow2) trading eval loss vs weight bytes.

    PYTHONPATH=src python examples/hw_approx_search_lm.py --arch qwen3-14b
"""
import argparse

import jax

from repro.configs import get_config
from repro.models import build_model
from repro.api import LMApproxSearch, FORMATS, enable_compile_cache
from repro.data.tokens import synthetic_token_batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--generations", type=int, default=6)
    ap.add_argument("--pop", type=int, default=16)
    args = ap.parse_args()

    cfg = get_config(args.arch).smoke()
    model = build_model(cfg, tp=1)
    params = model.init(jax.random.PRNGKey(0))
    b = synthetic_token_batch(0, 4, 64, cfg.vocab_size)
    batch = {k: jax.numpy.asarray(v) for k, v in b.items()}
    if cfg.n_codebooks > 1:
        batch = {k: jax.numpy.repeat(v[:, None], cfg.n_codebooks, 1)
                 for k, v in batch.items()}

    search = LMApproxSearch(model, params, batch, pop_size=args.pop)
    print(f"exact loss: {search.exact_loss:.4f}; "
          f"{search.n_genes} quantizable tensors")
    front = search.run(generations=args.generations)
    print("Pareto front (loss, MB, formats histogram):")
    for (loss, nbytes), g in zip(front["objectives"], front["genomes"]):
        hist = {FORMATS[f]: int((g == f).sum()) for f in range(3)}
        print(f"  loss={loss:.4f}  {nbytes / 1e6:7.2f} MB  {hist}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
