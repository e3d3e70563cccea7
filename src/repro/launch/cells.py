"""Cell builders: (architecture x input shape) -> lowerable jitted program.

A *cell* is one entry of the 40-cell dry-run grid.  ``build_cell`` returns a
``Cell`` whose ``lower(mesh)`` produces ``jax.jit(step).lower(*abstract)``
with every argument a ShapeDtypeStruct carrying a NamedSharding — no real
allocation ever happens (the full configs are exercised only this way).

Step kinds per family (configs/common.py shape tables):
  lm.train      full update step: loss -> grad -> AdamW (params+opt donated)
  lm.prefill    tokens -> (last logits, per-layer caches)
  lm.decode     one token against a seq_len KV/latent cache
  gnn.train     full update step over COO edges (full/sampled/batched modes)
  recsys.train  full update step (CTR loss)
  recsys.serve  batched scoring;  recsys.retrieval  1 query vs 1M candidates
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.common import shapes_for
from repro.launch import sharding as SH
from repro.launch.mesh import batch_axes
from repro.models import equivariant as EQ
from repro.models import gnn as GNN
from repro.models import recsys as RS
from repro.models import transformer as TF
from repro.training.optimizer import OptimizerConfig, adamw_update
from repro.graphs.sampler import union_caps


EDGE_PAD = 8192      # GNN edge arrays pad to this multiple (even sharding)


def _sds(shape, dtype, mesh, spec):
    spec = SH.sanitize_spec(spec, shape, mesh)
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _abstract_tree(tree, mesh, rule):
    """ShapeDtypeStructs (with shardings) for every leaf of a shape tree."""
    flat, tdef = jax.tree_util.tree_flatten_with_path(tree)
    out = [jax.ShapeDtypeStruct(
        l.shape, l.dtype,
        sharding=NamedSharding(mesh, SH.sanitize_spec(
            rule(jax.tree_util.keystr(p), l), l.shape, mesh)))
        for p, l in flat]
    return jax.tree_util.tree_unflatten(tdef, out)


def _abstract_opt(params_abs, mesh, rule):
    def f32_like(x, spec_rule_path):
        return x
    mu = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.float32,
                                       sharding=l.sharding), params_abs)
    return {"mu": mu, "nu": mu,
            "step": jax.ShapeDtypeStruct((), jnp.int32,
                                         sharding=NamedSharding(mesh, P()))}


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    kind: str
    step: Callable          # jit-able
    abstract_args: tuple    # ShapeDtypeStructs with shardings
    donate: tuple = ()
    static_notes: str = ""

    def lower(self, mesh: Mesh):
        with mesh:
            jitted = jax.jit(self.step, donate_argnums=self.donate)
            return jitted.lower(*self.abstract_args)


OPT = OptimizerConfig(lr=3e-4, warmup_steps=100, total_steps=10_000)


# ==========================================================================
# LM cells
# ==========================================================================

def _constrain(tree, mesh, rule):
    """with_sharding_constraint every leaf to its rule spec (weight-gather
    idiom: storage -> compute layout; grads transpose to reduce-scatter)."""
    def one(path, leaf):
        spec = SH.sanitize_spec(rule(jax.tree_util.keystr(path), leaf),
                                leaf.shape, mesh)
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, spec))
    return jax.tree_util.tree_map_with_path(one, tree)


def _lm_train_cell(arch, shp, mesh, cfg, microbatches: int = 1) -> Cell:
    B, L = shp["batch"], shp["seq_len"]
    params_abs = _abstract_tree(
        jax.eval_shape(lambda k: TF.init_params(k, cfg),
                       jax.random.PRNGKey(0)),
        mesh, SH.lm_param_spec)
    opt_abs = _abstract_opt(params_abs, mesh, SH.lm_param_spec)
    bspec = SH.lm_batch_spec(mesh)
    batch_abs = {"tokens": _sds((B, L), jnp.int32, mesh, bspec),
                 "labels": _sds((B, L), jnp.int32, mesh, bspec)}

    def loss_of(p, b):
        if cfg.fsdp_inner:          # per-layer gather inside the scan body
            p_tp = dict(p, embed=_constrain(p["embed"], mesh,
                                            SH.lm_param_spec_tp))
        else:                       # whole-tree gather at step start
            p_tp = _constrain(p, mesh, SH.lm_param_spec_tp)
        return TF.train_step_loss(p_tp, cfg, b)

    def step(params, opt_state, batch):
        if microbatches == 1:
            loss, grads = jax.value_and_grad(loss_of)(params, batch)
        else:
            # gradient accumulation: peak activations / microbatches.
            # Microbatches are SLICES of the sharded batch dim (size B/M
            # stays divisible by the data axes) — a (M, B/M, ...) reshape
            # would break the batch sharding (M < mesh data size).
            # STATIC slice offsets — traced (fori/scan) offsets defeat
            # GSPMD's alignment proof and it replicates the whole batch
            # (measured 16x cost blowup); an unrolled python loop keeps
            # every microbatch slice sharded exactly like its parent.
            mb_size = B // microbatches
            grads = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)
            loss = jnp.float32(0)
            for i in range(microbatches):
                mb = jax.tree.map(
                    lambda x: jax.lax.slice_in_dim(
                        x, i * mb_size, (i + 1) * mb_size, axis=0), batch)
                l, g = jax.value_and_grad(loss_of)(params, mb)
                grads = jax.tree.map(lambda a, x: a + x.astype(jnp.float32),
                                     grads, g)
                loss = loss + l
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss / microbatches
        params, opt_state, m = adamw_update(OPT, params, grads, opt_state)
        m["loss"] = loss
        return params, opt_state, m

    return Cell(arch, "train", "train", step,
                (params_abs, opt_abs, batch_abs), donate=(0, 1))


def _lm_prefill_cell(arch, shp, mesh, cfg) -> Cell:
    B, L = shp["batch"], shp["seq_len"]
    params_abs = _abstract_tree(                   # inference: pure TP
        jax.eval_shape(lambda k: TF.init_params(k, cfg),
                       jax.random.PRNGKey(0)),
        mesh, SH.lm_param_spec_tp)
    tokens_abs = _sds((B, L), jnp.int32, mesh, SH.lm_batch_spec(mesh))

    def step(params, tokens):
        return TF.prefill(params, cfg, tokens)

    return Cell(arch, "prefill", "prefill", step, (params_abs, tokens_abs))


def _lm_decode_cell(arch, shp, mesh, cfg) -> Cell:
    B, S = shp["batch"], shp["seq_len"]
    params_abs = _abstract_tree(                   # inference: pure TP
        jax.eval_shape(lambda k: TF.init_params(k, cfg),
                       jax.random.PRNGKey(0)),
        mesh, SH.lm_param_spec_tp)
    cache_shapes = jax.eval_shape(
        lambda: TF.make_empty_cache(cfg, B, S))
    cspec = SH.lm_cache_spec(mesh, cfg.attn_type, B, cfg.n_kv_heads)
    cache_abs = {k: jax.ShapeDtypeStruct(
        v.shape, v.dtype, sharding=NamedSharding(mesh, cspec[k]))
        for k, v in cache_shapes.items()}
    b_axes = batch_axes(mesh)
    bspec = P(b_axes) if B >= int(np.prod([mesh.shape[a] for a in b_axes])) \
        else P()
    tok_abs = _sds((B,), jnp.int32, mesh, bspec)
    len_abs = _sds((B,), jnp.int32, mesh, bspec)

    def step(params, token, cache, length):
        return TF.decode_step(params, cfg, token, cache, length)

    return Cell(arch, "decode", "decode", step,
                (params_abs, tok_abs, cache_abs, len_abs), donate=(2,))


# ==========================================================================
# GNN cells
# ==========================================================================

def _gnn_batch_shapes(arch_def, shp) -> dict:
    """Shape dict for one GNN cell's batch.

    Every graph gets one SINK padding node appended (index N-1) and edge
    arrays padded to a multiple of EDGE_PAD with sink->sink self-loops, so
    edge arrays shard evenly over the whole mesh and padding can never
    pollute real nodes (same trick as sampler.union_pad)."""
    mode = shp["mode"]
    model = arch_def.extras["model"]
    d_feat = shp["d_feat"]
    if mode == "full":
        N, E = shp["n_nodes"] + 1, shp["n_edges"]
        B = None
    elif mode == "sampled":
        caps = union_caps(shp["batch_nodes"],
                          tuple(reversed(shp["fanouts"])))
        N = caps[-1] + 1
        E = sum(c * f for c, f in zip(caps[:-1],
                                      tuple(reversed(shp["fanouts"]))))
        B = shp["batch_nodes"]
    else:                                     # batched molecules
        B = shp["batch"]
        N, E = B * shp["n_nodes"] + 1, B * shp["n_edges"]
    E = -(-E // EDGE_PAD) * EDGE_PAD
    out = {"src": (E,), "dst": (E,), "feats": (N, d_feat)}
    if mode != "batched":                     # batched target = energy
        out["labels"] = (B,) if mode == "sampled" else (N,)
    if model == "mgn":
        out["edge_feats"] = (E, 4)
    if model == "nequip":
        out["positions"] = (N, 3)
        out["species"] = (N,)
    if mode == "batched":
        out["graph_id"] = (N,)
        out["energy"] = (B,)
    if mode == "full":
        out["train_mask"] = (N,)
    return out, N


def _gnn_loss_fn(arch_def, shp, cfg, n_nodes):
    model = arch_def.extras["model"]
    mode = shp["mode"]

    def forward(params, batch):
        if model == "gat":
            out = GNN.gat_apply(params, cfg, batch["feats"], batch["src"],
                                batch["dst"], n_nodes)
        elif model == "mgn":
            out = GNN.mgn_apply(params, cfg, batch["feats"],
                                batch["edge_feats"], batch["src"],
                                batch["dst"], n_nodes)
        elif model == "gatedgcn":
            out = GNN.gatedgcn_apply(params, cfg, batch["feats"],
                                     batch["src"], batch["dst"], n_nodes)
        elif model == "nequip":
            e = EQ.nequip_apply(params, cfg, batch["species"],
                                batch["positions"], batch["src"],
                                batch["dst"], n_nodes,
                                scalar_feats=batch.get("feats"))
            return e[:, None]                     # (N, 1) scalar head
        else:
            raise ValueError(model)
        return out

    def loss(params, batch):
        out = forward(params, batch)
        if mode == "batched":
            e_graph = jax.ops.segment_sum(out.mean(-1), batch["graph_id"],
                                          batch["energy"].shape[0])
            return ((e_graph - batch["energy"]) ** 2).mean()
        if model == "nequip":                     # regression head elsewhere
            tgt = (batch["labels"] % 2).astype(jnp.float32)
            pred = out[: tgt.shape[0], 0]
            return ((pred - tgt) ** 2).mean()
        n_lab = batch["labels"].shape[0]
        logits = out[:n_lab]
        mask = batch.get("train_mask")
        mask = mask[:n_lab] if mask is not None else None
        return GNN.node_classification_loss(logits, batch["labels"], mask)

    return loss


def _gnn_halo_train_cell(arch, shp_name, shp, mesh, arch_def,
                         boundary_frac: float = 0.10) -> Cell:
    """Halo-exchange GatedGCN (shard_map): nodes block-partitioned, only
    boundary features exchanged (paper's replicated->halo trade, §Perf B).

    Shapes assume a block partition with ``boundary_frac`` of each shard's
    nodes on the boundary (mesh/ogb-class graphs; the real plan comes from
    core/partition.build_halo at run time)."""
    from jax import shard_map
    cfg = arch_def.make_full(d_in=shp["d_feat"], n_classes=shp["n_classes"])
    D = int(np.prod(list(mesh.shape.values())))
    axes = tuple(mesh.axis_names)
    N, E = shp["n_nodes"], shp["n_edges"]
    n_loc = -(-N // (D * 128)) * 128
    e_loc = -(-E // (D * 512)) * 512
    max_b = max(128, int(n_loc * boundary_frac) // 128 * 128)
    max_g = max_b                                 # symmetric estimate
    init = GNN.gatedgcn_init
    params_abs = _abstract_tree(
        jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0)),
        mesh, SH.gnn_param_spec)
    opt_abs = _abstract_opt(params_abs, mesh, SH.gnn_param_spec)
    shard = P(axes)
    batch_abs = {
        "feats": _sds((D * n_loc, cfg.d_in), jnp.float32, mesh, shard),
        "src": _sds((D * e_loc,), jnp.int32, mesh, shard),
        "dst": _sds((D * e_loc,), jnp.int32, mesh, shard),
        "boundary": _sds((D * max_b,), jnp.int32, mesh, shard),
        "ghost_flat": _sds((D * max_g,), jnp.int32, mesh, shard),
        "labels": _sds((D * n_loc,), jnp.int32, mesh, shard),
        "train_mask": _sds((D * n_loc,), jnp.float32, mesh, shard),
    }

    local_loss = functools.partial(GNN.gatedgcn_halo_loss, cfg=cfg,
                                   axis_names=axes, n_shards=D)
    sharded_loss = shard_map(
        lambda p, b: local_loss(p, batch=b),
        mesh=mesh,
        in_specs=(P(), {k: shard for k in batch_abs}),
        out_specs=P(), check_vma=False)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: sharded_loss(p, batch))(params)
        params, opt_state, m = adamw_update(OPT, params, grads, opt_state)
        m["loss"] = loss
        return params, opt_state, m

    return Cell(arch, shp_name, "train", step,
                (params_abs, opt_abs, batch_abs), donate=(0, 1),
                static_notes=f"halo boundary_frac={boundary_frac}")


def _gnn_train_cell(arch, shp_name, shp, mesh, arch_def,
                    overrides: Optional[dict] = None) -> Cell:
    gnn_opts = dict(overrides or {})           # cell-level gnn knobs
    if gnn_opts.pop("halo", False):
        if arch_def.extras["model"] != "gatedgcn" or shp["mode"] != "full":
            raise ValueError("halo variant: gatedgcn full-graph cells only")
        return _gnn_halo_train_cell(
            arch, shp_name, shp, mesh, arch_def,
            boundary_frac=float(gnn_opts.pop("boundary_frac", 0.10)))
    cfg = arch_def.make_full(d_in=shp["d_feat"], n_classes=shp["n_classes"])
    model = arch_def.extras["model"]
    init = {"gat": GNN.gat_init, "mgn": GNN.mgn_init,
            "gatedgcn": GNN.gatedgcn_init,
            "nequip": EQ.nequip_init}[model]
    params_abs = _abstract_tree(
        jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0)),
        mesh, SH.gnn_param_spec)
    opt_abs = _abstract_opt(params_abs, mesh, SH.gnn_param_spec)
    shapes, n_nodes = _gnn_batch_shapes(arch_def, shp)
    espec = SH.gnn_edge_spec(mesh)
    batch_abs = {}
    for k, s in shapes.items():
        if k in ("src", "dst"):
            batch_abs[k] = _sds(s, jnp.int32, mesh, espec)
        elif k == "edge_feats":
            batch_abs[k] = _sds(s, jnp.float32, mesh,
                                P(espec[0] if espec else None))
        elif k in ("labels", "species", "graph_id"):
            batch_abs[k] = _sds(s, jnp.int32, mesh, P())
        else:
            batch_abs[k] = _sds(s, jnp.float32, mesh, P())
    loss_fn = _gnn_loss_fn(arch_def, shp, cfg, n_nodes)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        params, opt_state, m = adamw_update(OPT, params, grads, opt_state)
        m["loss"] = loss
        return params, opt_state, m

    return Cell(arch, shp_name, "train", step,
                (params_abs, opt_abs, batch_abs), donate=(0, 1))


# ==========================================================================
# RecSys cells
# ==========================================================================

def _recsys_cells(arch, shp_name, shp, mesh, arch_def) -> Cell:
    cfg = arch_def.make_full()
    params_abs = _abstract_tree(
        jax.eval_shape(lambda k: RS.dcnv2_init(k, cfg),
                       jax.random.PRNGKey(0)),
        mesh, SH.recsys_param_spec)
    bspec = P(batch_axes(mesh))
    kind = shp["kind"]
    B = shp["batch"]
    dense_abs = _sds((B, cfg.n_dense), jnp.float32, mesh,
                     bspec if B >= 32 else P())
    sparse_abs = _sds((B, cfg.n_sparse, cfg.max_hots), jnp.int32, mesh,
                      bspec if B >= 32 else P())

    if kind == "train":
        opt_abs = _abstract_opt(params_abs, mesh, SH.recsys_param_spec)
        batch_abs = {"dense": dense_abs, "sparse": sparse_abs,
                     "labels": _sds((B,), jnp.int32, mesh, bspec)}

        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: RS.ctr_loss(p, cfg, batch))(params)
            params, opt_state, m = adamw_update(OPT, params, grads, opt_state)
            m["loss"] = loss
            return params, opt_state, m

        return Cell(arch, shp_name, "train", step,
                    (params_abs, opt_abs, batch_abs), donate=(0, 1))

    if kind == "serve":
        batch_abs = {"dense": dense_abs, "sparse": sparse_abs}

        def step(params, batch):
            return RS.predict(params, cfg, batch)

        return Cell(arch, shp_name, "serve", step, (params_abs, batch_abs))

    # retrieval: 1 query vs n_candidates
    NC = shp["n_candidates"]
    cand_abs = _sds((NC, cfg.mlp_dims[-1]), jnp.float32, mesh,
                    P(tuple(mesh.axis_names)))

    def step(params, dense, sparse, cand):
        return RS.retrieval_scores(params, cfg, dense, sparse, cand,
                                   top_k=100)

    return Cell(arch, shp_name, "retrieval", step,
                (params_abs, dense_abs, sparse_abs, cand_abs))


# ==========================================================================
# entry point
# ==========================================================================

def build_cell(arch: str, shape: str, mesh: Mesh,
               overrides: Optional[dict] = None) -> Cell:
    """``overrides``: model-config fields to replace (perf-knob variants for
    the §Perf hillclimb, e.g. {"wire_barrier": True})."""
    arch_def = configs.get(arch)
    shp = dict(shapes_for(arch_def.family)[shape])
    if arch_def.family == "lm":
        overrides = dict(overrides or {})
        microbatches = int(overrides.pop("microbatches", 1))
        moe_ep = overrides.pop("moe_ep", False)
        cfg = arch_def.make_full()
        if moe_ep and cfg.moe is not None:   # EP: experts x capacity shard
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe,
                                             ep_axes=("model", "data")))
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        if getattr(cfg, "act_shard", False) and not cfg.act_batch_axes:
            cfg = dataclasses.replace(cfg, act_batch_axes=batch_axes(mesh))
        if getattr(cfg, "fsdp_inner", False):
            cfg = dataclasses.replace(cfg,
                                      model_axis_size=mesh.shape["model"])
        if shp["kind"] == "train":
            return _lm_train_cell(arch, shp, mesh, cfg,
                                  microbatches=microbatches)
        if shp["kind"] == "prefill":
            return _lm_prefill_cell(arch, shp, mesh, cfg)
        return _lm_decode_cell(arch, shp, mesh, cfg)
    if arch_def.family == "gnn":
        return _gnn_train_cell(arch, shape, shp, mesh, arch_def,
                               overrides=overrides)
    return _recsys_cells(arch, shape, shp, mesh, arch_def)
