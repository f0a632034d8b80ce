"""Training driver: end-to-end training on the local mesh.

Two arms share one CLI:
  * transformer archs (default): reduced-config LM training
    (examples/train_transformer.py drives it for the ~100M-param example);
  * ``--arch federated-forest``: tabular federated training through the
    Federation session API (ingest -> fit -> one-round predict), with an
    optional ``--ckpt-dir`` break-point-recoverable fit (paper §4.1).

Production launch is the same code against make_production_mesh().
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import registry
from repro.configs.base import ArchConfig, reduced
from repro.data.lm import synthetic_lm_batches
from repro.models import transformer
from repro.train import optim
from repro.train.step import make_train_step


def train_loop(cfg: ArchConfig, *, steps: int, batch: int, seq: int,
               lr: float = 1e-3, micro_batch: int = 0, seed: int = 0,
               log_every: int = 10):
    params = transformer.init_params(jax.random.key(seed), cfg)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params / 1e6:.1f}M")
    opt = optim.adamw_init(params)
    step_fn = jax.jit(make_train_step(cfg, micro_batch=micro_batch, lr=lr))

    losses = []
    t0 = time.time()
    for i, b in enumerate(synthetic_lm_batches(cfg, batch, seq, seed=seed)):
        if i >= steps:
            break
        params, opt, metrics = step_fn(params, opt, b)
        if i % log_every == 0 or i == steps - 1:
            ce = float(metrics["ce"])
            losses.append(ce)
            tok_s = batch * seq * (i + 1) / (time.time() - t0)
            print(f"step {i:4d}  ce={ce:.4f}  tok/s={tok_s:,.0f}")
    return params, losses


def parse_party_csvs(specs, id_column: str, label_column: str) -> list:
    """``NAME=PATH`` (or bare PATH) CLI specs -> CSVSource list.

    Split at the FIRST ``=`` — party names cannot contain one, but paths
    can (``bank=/data/run=3/bank.csv``).  A spec whose pre-``=`` part
    contains a path separator is a bare path (``/data/run=3/bank.csv``);
    a bare *relative* path with ``=`` before any separator needs an
    explicit ``NAME=``."""
    import os as _os
    from repro.core.partyblock import CSVSource
    sources = []
    for spec in specs:
        name, sep, path = spec.partition("=")
        if not sep or "/" in name or _os.sep in name:
            name, path = None, spec
        sources.append(CSVSource(path, name=name or None,
                                 id_column=id_column,
                                 label_column=label_column))
    return sources


def forest_train(args) -> None:
    """Federated-forest training through the Federation session API.

    Two ingest shapes: synthetic raw-matrix data (default), or party-first
    per-party CSV extracts (``--party-csv name=path``, repeated) — rows
    keyed by ``--id-column``, aligned on hashed IDs, labels taken from
    whichever party's CSV carries ``--label-column``."""
    from repro.core import ForestParams
    from repro.data import make_classification
    from repro.data.metrics import accuracy
    from repro.data.tabular import train_test_split
    from repro.federation import Federation

    p = ForestParams(n_estimators=args.trees, max_depth=args.depth,
                     n_bins=16, seed=args.seed)
    if args.party_csv:
        sources = parse_party_csvs(args.party_csv, args.id_column,
                                   args.label_column)
        fed = Federation(parties=len(sources), n_bins=p.n_bins)
        part = fed.ingest(sources)
        print(f"aligned {part.n_samples} common samples across "
              f"{part.n_parties} parties {list(part.party_names)}")
        t0 = time.time()
        model = fed.fit_resumable(p, args.ckpt_dir) if args.ckpt_dir \
            else fed.fit(p)
        t_fit = time.time() - t0
        acc = accuracy(fed.labels_, fed.predict(model, part.dense_raw()))
        print(f"federated-forest: {args.trees} trees x depth {args.depth} "
              f"over {part.n_parties} parties in {t_fit:.1f}s  "
              f"train-acc={acc:.3f}")
        return
    x, y = make_classification(args.rows, args.features, 2,
                               n_informative=max(4, args.features // 3),
                               seed=args.seed)
    xtr, ytr, xte, yte = train_test_split(x, y, 0.25, seed=args.seed)

    fed = Federation(parties=args.parties, n_bins=p.n_bins)
    fed.ingest(xtr, ytr)
    t0 = time.time()
    if args.ckpt_dir:
        model = fed.fit_resumable(p, args.ckpt_dir)
    else:
        model = fed.fit(p)
    t_fit = time.time() - t0
    acc = accuracy(yte, fed.predict(model, xte))
    print(f"federated-forest: {args.trees} trees x depth {args.depth} over "
          f"{args.parties} parties in {t_fit:.1f}s  acc={acc:.3f}")
    assert acc > 0.5, "federated fit degenerated"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    # federated-forest arm
    ap.add_argument("--parties", type=int, default=3)
    ap.add_argument("--trees", type=int, default=8)
    ap.add_argument("--depth", type=int, default=6)
    ap.add_argument("--rows", type=int, default=2000)
    ap.add_argument("--features", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None,
                    help="forest arm: break-point-recoverable fit directory")
    ap.add_argument("--party-csv", action="append", default=None,
                    metavar="NAME=PATH",
                    help="forest arm: per-party CSV extract (repeat once "
                         "per party); rows are aligned on hashed "
                         "--id-column values, the one CSV carrying "
                         "--label-column holds the labels")
    ap.add_argument("--id-column", default="id")
    ap.add_argument("--label-column", default="label")
    args = ap.parse_args()
    enable_compile_cache()
    if args.arch == "federated-forest":
        forest_train(args)
        return
    cfg = registry.get(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    _, losses = train_loop(cfg, steps=args.steps, batch=args.batch,
                           seq=args.seq, lr=args.lr)
    assert losses[-1] < losses[0], "training diverged"
    print(f"done: ce {losses[0]:.3f} -> {losses[-1]:.3f}")


if __name__ == "__main__":
    main()
