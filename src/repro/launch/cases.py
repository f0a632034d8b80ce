"""Dry-run case builder: (arch × input-shape × mesh) -> lowerable closure.

Everything is ShapeDtypeStruct-based (jax.eval_shape) — no device memory is
allocated; ``lower().compile()`` is the proof that the distribution config is
coherent (deliverable (e)).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import registry
from repro.configs.base import ArchConfig
from repro.models import sharding, transformer
from repro.serve import step as serve_step_mod
from repro.train import optim
from repro.train.step import make_train_step


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    kind: str          # train | prefill | decode
    seq: int
    batch: int
    subquadratic: bool = False   # long-context: require sub-quadratic path


SHAPES: dict[str, InputShape] = {
    "train_4k":    InputShape("train_4k", "train", 4_096, 256),
    "prefill_32k": InputShape("prefill_32k", "prefill", 32_768, 32),
    "decode_32k":  InputShape("decode_32k", "decode", 32_768, 128),
    "long_500k":   InputShape("long_500k", "decode", 524_288, 1, True),
}

# principled skips (DESIGN.md §5)
SKIPS: dict[tuple[str, str], str] = {
    ("whisper-large-v3", "long_500k"):
        "enc-dec audio: decoder caps at 448 tokens; no faithful "
        "sub-quadratic variant of cross+self attention at 500k",
}

SWA_WINDOW = 4_096
TRAIN_MICRO_BATCH = 8


class Skip(Exception):
    pass


def arch_for_shape(arch: str, shape: InputShape) -> ArchConfig:
    """Resolve the per-shape config variant (e.g. SWA for long_500k)."""
    if (arch, shape.name) in SKIPS:
        raise Skip(SKIPS[(arch, shape.name)])
    cfg = registry.get(arch)
    if shape.subquadratic and not cfg.is_subquadratic:
        # sliding-window variant for the attention blocks (hybrid archs keep
        # full recurrent state in their SSM blocks)
        cfg = cfg.with_(sliding_window=SWA_WINDOW)
    return cfg


@dataclasses.dataclass
class Case:
    arch: str
    shape: InputShape
    cfg: ArchConfig
    fn: Callable
    args: tuple              # ShapeDtypeStructs
    in_shardings: Any
    out_shardings: Any
    donate: tuple = ()

    def lower(self, mesh: Mesh):
        ins = sharding.named(mesh, self.in_shardings)
        outs = sharding.named(mesh, self.out_shardings)
        jitted = jax.jit(self.fn, in_shardings=ins, out_shardings=outs,
                         donate_argnums=self.donate)
        with jax.set_mesh(mesh):  # resolves in-model sharding constraints
            return jitted.lower(*self.args)


def _batch_struct(cfg: ArchConfig, shape: InputShape) -> dict:
    b, s = shape.batch, shape.seq
    out = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    if cfg.enc_layers:
        out["frames"] = jax.ShapeDtypeStruct((b, cfg.enc_frames, cfg.d_model),
                                             jnp.dtype(cfg.dtype))
    if cfg.n_patches:
        out["patches"] = jax.ShapeDtypeStruct((b, cfg.n_patches, cfg.d_model),
                                              jnp.dtype(cfg.dtype))
    return out


def _batch_specs(batch: dict, shape: InputShape, mesh: Mesh):
    return {k: sharding.batch_spec(shape.batch, mesh, extra_dims=v.ndim - 1)
            for k, v in batch.items()}


def input_specs(arch: str, shape_name: str, mesh: Mesh,
                overrides: Optional[dict] = None,
                micro_batch: Optional[int] = None,
                serve_layout: Optional[bool] = None,
                expert_data: bool = False) -> Case:
    """ShapeDtypeStruct stand-ins + shardings for every model input.

    ``overrides``: ArchConfig field overrides (§Perf variants).
    ``micro_batch``: grad-accumulation microbatch override for train.
    ``serve_layout``: tensor-parallel-only param shardings. §Perf tested and
    REFUTED this as a default: it removes the serving all-reduces but
    replicates weights (dense: +10 GiB/dev) and, for MoE, de-shards the
    dispatch tensors (back to 88 GiB/dev) — the FSDP layout's data-dim
    propagation was load-bearing. Kept as an experiment flag.
    """
    shape = SHAPES[shape_name]
    cfg = arch_for_shape(arch, shape)
    if overrides:
        cfg = cfg.with_(**overrides)

    if serve_layout is None:
        serve_layout = False
    pshapes = jax.eval_shape(
        lambda k: transformer.init_params(k, cfg), jax.random.key(0))
    pspecs = sharding.param_specs(
        pshapes, mesh, mode="serve" if serve_layout else "train",
        expert_data=expert_data)

    if shape.kind == "train":
        oshapes = jax.eval_shape(optim.adamw_init, pshapes)
        ospecs = sharding.opt_specs(oshapes, pspecs)
        batch = _batch_struct(cfg, shape)
        bspecs = _batch_specs(batch, shape, mesh)
        mb = micro_batch or min(TRAIN_MICRO_BATCH, shape.batch)
        fn = make_train_step(cfg, micro_batch=mb)
        return Case(arch, shape, cfg, fn, (pshapes, oshapes, batch),
                    in_shardings=(pspecs, ospecs, bspecs),
                    out_shardings=(pspecs, ospecs, P()),
                    donate=(0, 1))

    if shape.kind == "prefill":
        batch = _batch_struct(cfg, shape)
        bspecs = _batch_specs(batch, shape, mesh)
        fn = serve_step_mod.make_prefill_step(cfg)
        cshapes = jax.eval_shape(
            lambda: transformer.make_cache(cfg, shape.batch, shape.seq))
        cspecs = sharding.cache_specs(cshapes, shape.batch, mesh)
        lspec = sharding.batch_spec(shape.batch, mesh, extra_dims=1)
        return Case(arch, shape, cfg, fn, (pshapes, batch),
                    in_shardings=(pspecs, bspecs),
                    out_shardings=(lspec, cspecs))

    # decode
    cshapes = jax.eval_shape(
        lambda: transformer.make_cache(cfg, shape.batch, shape.seq))
    cspecs = sharding.cache_specs(cshapes, shape.batch, mesh)
    token = jax.ShapeDtypeStruct((shape.batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    tspec = sharding.batch_spec(shape.batch, mesh, extra_dims=1)
    fn = serve_step_mod.make_serve_step(cfg)
    return Case(arch, shape, cfg, fn, (pshapes, cshapes, token, pos),
                in_shardings=(pspecs, cspecs, tspec, P()),
                out_shardings=(tspec, cspecs),
                donate=(1,))


# --------------------------------------------------- federated forest case
@dataclasses.dataclass(frozen=True)
class ForestShape:
    name: str
    n_samples: int
    n_feat_per_party: int
    n_trees_per_shard: int
    n_test: int = 0


FOREST_SHAPES = {
    "ff_train": ForestShape("ff_train", 262_144, 16, 4),
    "ff_predict": ForestShape("ff_predict", 262_144, 16, 4, n_test=65_536),
}


def forest_case(shape_name: str, mesh: Mesh, params=None, *,
                hist_impl: str = "scatter", **predict_kw):
    """Lowerable federated-forest protocol on the (trees, parties) mesh.

    Layout: the 'parties' axis carries the vertical feature partition (the
    paper's clients); the 'trees' axis carries bagging tree-parallelism; a
    'pod' axis (if present) replicates.  Party-private outputs keep a
    leading parties dim; tree-sharded inputs/outputs use their leading
    T dim.  The programs come from a sharded-substrate Federation session —
    the same code path production serving compiles.  Returns
    (fn, args, forest_params); ``predict_kw`` (compact / mask_dtype /
    vote_impl) goes to Federation.predict_program, with ``compact=True``
    appending the LeafTable leaf_idx ShapeDtypeStruct to args.
    """
    from repro.core.types import ForestParams
    from repro.federation import Federation

    fs = FOREST_SHAPES[shape_name]
    p = params or ForestParams(task="classification", n_classes=2,
                               n_estimators=fs.n_trees_per_shard, max_depth=8,
                               n_bins=32)
    m = mesh.shape["parties"]
    t_global = fs.n_trees_per_shard * mesh.shape["trees"]
    n, fp = fs.n_samples, fs.n_feat_per_party
    f_total = m * fp
    fed = Federation(parties=m, substrate="sharded", mesh=mesh)

    fit_args = (
        jax.ShapeDtypeStruct((m, n, fp), jnp.uint8),             # xb (by party)
        jax.ShapeDtypeStruct((m, fp), jnp.int32),                # feat_gid
        jax.ShapeDtypeStruct((t_global, f_total), jnp.bool_),    # feat_sel
        jax.ShapeDtypeStruct((t_global, n), jnp.float32),        # weights
        jax.ShapeDtypeStruct((n, p.n_stat_channels), jnp.float32),  # y_stats
    )
    fit_sharded = fed.fit_program(p, hist_impl=hist_impl)

    if shape_name == "ff_train":
        return fit_sharded, fit_args, p

    trees_shape = jax.eval_shape(fit_sharded, *fit_args)
    predict = fed.predict_program(p, **predict_kw)
    xb_test = jax.ShapeDtypeStruct((m, fs.n_test, fp), jnp.uint8)
    args = (trees_shape, xb_test)
    if predict_kw.get("compact"):
        # serving-engine leaf table at full bottom-level capacity — the
        # worst-case compact lowering (2^depth slots vs 2^(depth+1)-1)
        args += (jax.ShapeDtypeStruct((t_global, 2 ** p.max_depth),
                                      jnp.int32),)
    return predict, args, p
