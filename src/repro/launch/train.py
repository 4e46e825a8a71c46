"""Training launcher: `--arch <id>` resolves the registry, builds the data
pipeline for the family, and trains under checkpoint/restart supervision.

CPU-scale runs use the smoke config by default (`--full` selects the real
one — on this container that is only practical for the dry-run, which is
`repro.launch.dryrun`'s job).

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --steps 50
    PYTHONPATH=src python -m repro.launch.train --arch gin-tu --shape molecule
    PYTHONPATH=src python -m repro.launch.train --arch fm --steps 30
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.registry import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.train import OptimizerConfig
from repro.train.train_loop import fit


def _lm_setup(cfg, batch, seq):
    from repro.data.lm_data import lm_batches
    from repro.models import transformer as tfm
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    data = lm_batches(cfg.vocab, batch=batch, seq_len=seq, seed=0)
    return params, data, lambda p, b: tfm.loss_fn(cfg, p, b)


def _gnn_setup(cfg, shape_name):
    from repro.data import graph_data
    from repro.models import gnn
    if shape_name == "molecule":
        cfg = dataclasses.replace(cfg, graph_readout=True)
        params = gnn.init_params(cfg, jax.random.PRNGKey(0))

        def gen():
            seed = 0
            while True:
                b = graph_data.molecule_batch(8, 12, 24, cfg.d_feat,
                                              cfg.n_classes, seed=seed)
                seed += 1
                yield {k: v for k, v in b.items() if k != "n_graphs"}

        extra = {"n_graphs": 8}
        return params, gen(), (lambda p, b: gnn.loss_fn(cfg, p, dict(b, **extra)))
    g = graph_data.generate_graph(600, 4000, cfg.d_feat, cfg.n_classes, seed=0)
    params = gnn.init_params(cfg, jax.random.PRNGKey(0))
    if shape_name == "minibatch_lg":
        rng = np.random.default_rng(0)

        def gen():
            while True:
                seeds = rng.integers(0, g.n_nodes, 32)
                yield graph_data.sample_subgraph(g, seeds, (5, 3), rng)

        return params, gen(), (lambda p, b: gnn.loss_fn(cfg, p, b))

    full = graph_data.full_graph_batch(g)

    def gen():
        while True:
            yield full

    return params, gen(), (lambda p, b: gnn.loss_fn(cfg, p, b))


def _recsys_setup(cfg, batch):
    from repro.data.recsys_data import ClickLog
    from repro.models import recsys
    params = recsys.init_params(cfg, jax.random.PRNGKey(0))
    log = ClickLog(cfg.field_vocabs, item_vocab=cfg.item_vocab,
                   seq_len=cfg.seq_len, seed=0)
    seq = cfg.model in ("bst", "mind")

    def gen():
        while True:
            yield log.seq_batch(batch) if seq else log.ctr_batch(batch)

    return params, gen(), (lambda p, b: recsys.loss_fn(cfg, p, b))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default=None, help="gnn: which graph regime")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--full", action="store_true",
                    help="full-scale config (dry-run scale; not for CPU)")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    spec = get_arch(args.arch)
    cfg = spec.make_config() if args.full else spec.make_smoke_config()
    if spec.family == "lm":
        params, data, loss_fn = _lm_setup(cfg, args.batch, args.seq)
    elif spec.family == "gnn":
        params, data, loss_fn = _gnn_setup(cfg, args.shape or "full_graph_sm")
    elif spec.family == "recsys":
        params, data, loss_fn = _recsys_setup(cfg, args.batch)
    else:
        raise SystemExit(f"--arch {args.arch}: family {spec.family} is served, "
                         "not trained (see repro.launch.serve)")

    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    print(f"[train] arch={args.arch} family={spec.family} params={n_params:,}")
    ckpt = CheckpointManager(args.ckpt, keep=2) if args.ckpt else None
    _, _, hist = fit(params, loss_fn,
                     OptimizerConfig(lr=args.lr, warmup_steps=5,
                                     decay_steps=max(args.steps, 10)),
                     data, n_steps=args.steps, ckpt=ckpt, log_every=10)
    print(f"[train] loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
