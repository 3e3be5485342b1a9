"""Step factory: (arch, shape) -> init / step callables + input specs.

This is the single place that knows how every architecture family maps onto
train/serve steps, what its batch pytree looks like, and how to fabricate
both ShapeDtypeStruct specs and concrete synthetic batches (smoke tests,
examples).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchSpec, ShapeSpec
from repro.models import gcn as gcn_mod
from repro.models import transformer as tfm
from repro.models.gcn import GCNConfig
from repro.models.recsys import dcn, dlrm, mind, sasrec
from repro.training import train_loop
from repro.training.optimizer import AdamWConfig

F32 = jnp.float32
I32 = jnp.int32


@dataclasses.dataclass
class StepBundle:
    """Everything needed to lower or run one (arch x shape) cell."""

    arch_id: str
    shape_name: str
    kind: str                       # train | serve
    init_fn: Callable[[jax.Array], Any]
    step_fn: Callable[..., Any]     # train: (params, opt, batch); serve: (params, [cache,] batch)
    batch_spec: Dict[str, jax.ShapeDtypeStruct]
    make_batch: Callable[[jax.Array], Dict[str, jax.Array]]
    cache_spec: Optional[Dict[str, jax.ShapeDtypeStruct]] = None
    model_flops_per_step: float = 0.0   # 6*N*D style model FLOPs
    notes: str = ""
    opt_cfg: Optional[AdamWConfig] = None   # the config step_fn actually uses


DEFAULT_OPT = AdamWConfig(moment_dtype=jnp.bfloat16)
SMOKE_OPT = AdamWConfig(moment_dtype=jnp.float32, warmup_steps=2, total_steps=100)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------

def _reduce_lm_shape(shape: ShapeSpec) -> ShapeSpec:
    table = {
        "lm_train": dict(seq_len=32, global_batch=4),
        "lm_prefill": dict(seq_len=64, global_batch=2),
        "lm_decode": dict(seq_len=64, global_batch=2),
    }
    t = table[shape.kind]
    return dataclasses.replace(shape, **t)


def _lm_bundle(arch: ArchSpec, shape: ShapeSpec, cfg: tfm.TransformerConfig,
               opt_cfg: AdamWConfig) -> StepBundle:
    b, s = shape.global_batch, shape.seq_len
    init_fn = lambda key: tfm.init(cfg, key)
    n_params_active = cfg.active_param_count()

    if shape.kind == "lm_train":
        spec = dict(
            tokens=_sds((b, s), I32), labels=_sds((b, s), I32),
            mask=_sds((b, s), F32),
        )
        # gradient accumulation scales activation memory down with model
        # size (grok-314B at mb=1 needs ~62 GB/chip of temps; mb=8 fits),
        # and the biggest models also take reduced-precision optimizer
        # state (fp8 mu per FP8-LM, bf16 nu, bf16 grad accumulation).
        n_params = cfg.param_count()
        mb = 8 if n_params > 1.2e11 else 4 if n_params > 6e10 else \
            2 if n_params > 1.5e10 else 1
        mb = mb if b % max(mb, 1) == 0 else 1
        accum = jnp.float32
        if n_params > 6e10 and opt_cfg is DEFAULT_OPT:
            opt_cfg = dataclasses.replace(
                opt_cfg, mu_dtype=jnp.float8_e4m3fn, nu_dtype=jnp.bfloat16,
            )
            accum = jnp.bfloat16
        grad_pspecs = None
        if cfg.act_shard is not None:
            # shard the grad accumulator like the params: without this the
            # microbatch loop all-reduces *full* layer grads (see train_loop)
            from repro.distributed import sharding as shpol
            pshape = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
            grad_pspecs = shpol.param_specs("lm", pshape, cfg)
        step = train_loop.make_train_step(
            functools.partial(tfm.loss_fn, cfg), opt_cfg, microbatches=mb,
            accum_dtype=accum, grad_pspecs=grad_pspecs,
        )

        def make_batch(key):
            toks = jax.random.randint(key, (b, s), 0, cfg.vocab, I32)
            return dict(tokens=toks, labels=jnp.roll(toks, -1, axis=1),
                        mask=jnp.ones((b, s), F32))

        flops = 6.0 * n_params_active * b * s  # fwd+bwd 6ND
        return StepBundle(arch.id, shape.name, "train", init_fn, step, spec,
                          make_batch, model_flops_per_step=flops,
                          opt_cfg=opt_cfg)

    if shape.kind == "lm_prefill":
        spec = dict(tokens=_sds((b, s), I32))

        def serve_prefill(params, batch):
            h, _ = tfm.forward(cfg, params, batch["tokens"])
            logits = (h[:, -1:, :].astype(cfg.compute_dtype)
                      @ params["lm_head"]["w"].astype(cfg.compute_dtype))
            return logits

        def make_batch(key):
            return dict(tokens=jax.random.randint(key, (b, s), 0, cfg.vocab, I32))

        flops = 2.0 * n_params_active * b * s
        return StepBundle(arch.id, shape.name, "serve", init_fn, serve_prefill,
                          spec, make_batch, model_flops_per_step=flops)

    if shape.kind == "lm_decode":
        # int8 KV cache with per-token scales whenever the bf16 cache would
        # exceed ~0.5 TB globally (qwen's MHA at 32k is 5.5 TB; grok /
        # command-r / dbrx land 0.7-1.1 TB).
        cache_bytes_bf16 = (cfg.n_layers * b * s * cfg.n_kv_heads
                            * cfg.hd * 2 * 2)
        if cache_bytes_bf16 > 0.5e12 and cfg.compute_dtype == jnp.bfloat16:
            cfg = dataclasses.replace(cfg, kv_quant=True)
        cache_dt = jnp.bfloat16 if cfg.compute_dtype == jnp.bfloat16 else F32
        cshape = (cfg.n_layers, b, s, cfg.n_kv_heads, cfg.hd)
        if cfg.kv_quant:
            sshape = (cfg.n_layers, b, s, cfg.n_kv_heads)
            cache_spec = dict(
                k=_sds(cshape, jnp.int8), v=_sds(cshape, jnp.int8),
                k_scale=_sds(sshape, jnp.bfloat16),
                v_scale=_sds(sshape, jnp.bfloat16),
                length=_sds((), I32),
            )
        else:
            cache_spec = dict(k=_sds(cshape, cache_dt),
                              v=_sds(cshape, cache_dt),
                              length=_sds((), I32))
        spec = dict(tokens=_sds((b, 1), I32))

        def serve_decode(params, cache, batch):
            return tfm.decode_step(cfg, params, cache, batch["tokens"])

        def make_batch(key):
            return dict(tokens=jax.random.randint(key, (b, 1), 0, cfg.vocab, I32))

        flops = 2.0 * n_params_active * b  # one token per row
        return StepBundle(arch.id, shape.name, "serve", init_fn, serve_decode,
                          spec, make_batch, cache_spec=cache_spec,
                          model_flops_per_step=flops)
    raise ValueError(shape.kind)


# ---------------------------------------------------------------------------
# GNN family
# ---------------------------------------------------------------------------

def _gnn_cfg(template, shape: ShapeSpec, reduced: bool) -> GCNConfig:
    x = shape.extra
    return GCNConfig(
        n_layers=template.n_layers, d_feat=x["d_feat"],
        d_hidden=template.d_hidden, n_classes=x["n_classes"],
        aggregator="sym" if shape.kind == "gnn_full" else "mean",
        readout="mean" if shape.kind == "gnn_batched" else None,
        compute_dtype=template.compute_dtype,
    )


def _reduce_gnn_shape(shape: ShapeSpec) -> ShapeSpec:
    x = dict(shape.extra)
    if shape.kind == "gnn_full":
        x.update(n_nodes=120, n_edges=480, d_feat=32, n_classes=7)
    elif shape.kind == "gnn_minibatch":
        x.update(n_nodes=500, n_edges=4000, batch_nodes=8, fanout=(3, 2),
                 d_feat=16, n_classes=5)
    else:  # batched molecules
        x.update(n_nodes=10, n_edges=16, batch=8, d_feat=8, n_classes=2)
    return dataclasses.replace(shape, extra=x)


def _gnn_bundle(arch: ArchSpec, shape: ShapeSpec, template,
                opt_cfg: AdamWConfig, reduced: bool) -> StepBundle:
    cfg = _gnn_cfg(template, shape, reduced)
    init_fn = lambda key: gcn_mod.init(cfg, key)
    x = shape.extra

    if shape.kind == "gnn_full":
        # pad node/edge counts to 512-multiples: explicit input shardings
        # need divisibility; masks keep the math exact on the padding
        n = ((x["n_nodes"] + 511) // 512) * 512
        m = ((x["n_edges"] + 511) // 512) * 512
        n_real, m_real = x["n_nodes"], x["n_edges"]
        spec = dict(
            features=_sds((n, cfg.d_feat), F32),
            edge_src=_sds((m,), I32), edge_dst=_sds((m,), I32),
            edge_mask=_sds((m,), F32),
            labels=_sds((n,), I32), label_mask=_sds((n,), F32),
        )
        step = train_loop.make_train_step(
            functools.partial(gcn_mod.loss_full, cfg), opt_cfg
        )

        def make_batch(key):
            k1, k2, k3 = jax.random.split(key, 3)
            return dict(
                features=jax.random.normal(k1, (n, cfg.d_feat), F32),
                edge_src=jax.random.randint(k2, (m,), 0, n_real, I32),
                edge_dst=jax.random.randint(k3, (m,), 0, n_real, I32),
                edge_mask=(jnp.arange(m) < m_real).astype(F32),
                labels=jax.random.randint(k1, (n,), 0, cfg.n_classes, I32),
                label_mask=(jnp.arange(n) < n_real).astype(F32),
            )

        # SpMM flops: 2 * m * d per layer (gather-mac) + dense n*d_in*d_out
        dims = [cfg.d_feat] + [cfg.d_hidden] * (cfg.n_layers - 1) + [cfg.n_classes]
        flops = 3.0 * sum(
            2.0 * m * dims[i] + 2.0 * n * dims[i] * dims[i + 1]
            for i in range(cfg.n_layers)
        )  # x3 for fwd+bwd
        return StepBundle(arch.id, shape.name, "train", init_fn, step, spec,
                          make_batch, model_flops_per_step=flops,
                          opt_cfg=opt_cfg)

    if shape.kind == "gnn_minibatch":
        seeds = x["batch_nodes"]
        f1, f2 = x["fanout"]
        n1 = seeds + seeds * f1                 # block-1 node set
        n2 = n1 + n1 * f2                       # block-2 node set
        e1, e2 = seeds * f1, n1 * f2
        spec = dict(
            feats=_sds((n2, cfg.d_feat), F32),
            e2_src=_sds((e2,), I32), e2_dst=_sds((e2,), I32),
            e2_mask=_sds((e2,), F32),
            e1_src=_sds((e1,), I32), e1_dst=_sds((e1,), I32),
            e1_mask=_sds((e1,), F32),
            labels=_sds((seeds,), I32),
        )

        def loss(params, batch):
            blocks_edges = [
                dict(edge_src=batch["e1_src"], edge_dst=batch["e1_dst"],
                     edge_mask=batch["e1_mask"], n_dst=seeds),
                dict(edge_src=batch["e2_src"], edge_dst=batch["e2_dst"],
                     edge_mask=batch["e2_mask"], n_dst=n1),
            ]
            logits = gcn_mod.forward_sampled(
                cfg, params, [None, batch["feats"]], blocks_edges
            )
            from repro.models import layers as L
            return L.softmax_cross_entropy(logits, batch["labels"])

        step = train_loop.make_train_step(loss, opt_cfg)

        def make_batch(key):
            ks = jax.random.split(key, 4)
            return dict(
                feats=jax.random.normal(ks[0], (n2, cfg.d_feat), F32),
                e2_src=jax.random.randint(ks[1], (e2,), 0, n2, I32),
                e2_dst=jax.random.randint(ks[1], (e2,), 0, n1, I32),
                e2_mask=jnp.ones((e2,), F32),
                e1_src=jax.random.randint(ks[2], (e1,), 0, n1, I32),
                e1_dst=jax.random.randint(ks[2], (e1,), 0, seeds, I32),
                e1_mask=jnp.ones((e1,), F32),
                labels=jax.random.randint(ks[3], (seeds,), 0, cfg.n_classes, I32),
            )

        flops = 3.0 * (2.0 * e2 * cfg.d_feat + 2.0 * n1 * cfg.d_feat * cfg.d_hidden
                       + 2.0 * e1 * cfg.d_hidden
                       + 2.0 * seeds * cfg.d_hidden * cfg.n_classes)
        return StepBundle(arch.id, shape.name, "train", init_fn, step, spec,
                          make_batch, model_flops_per_step=flops,
                          opt_cfg=opt_cfg)

    # batched molecules
    bsz, npg, epg = x["batch"], x["n_nodes"], x["n_edges"]
    n, m = bsz * npg, bsz * epg * 2
    spec = dict(
        features=_sds((n, cfg.d_feat), F32),
        edge_src=_sds((m,), I32), edge_dst=_sds((m,), I32),
        edge_mask=_sds((m,), F32),
        graph_ids=_sds((n,), I32), graph_labels=_sds((bsz,), I32),
    )

    def loss(params, batch):
        return gcn_mod.loss_full(cfg, params, batch)

    step = train_loop.make_train_step(loss, opt_cfg)

    def make_batch(key):
        ks = jax.random.split(key, 3)
        gid = jnp.repeat(jnp.arange(bsz, dtype=I32), npg)
        edge_off = jnp.repeat(jnp.arange(bsz, dtype=I32) * npg, 2 * epg)
        src = jax.random.randint(ks[0], (m,), 0, npg, I32)
        dst = jax.random.randint(ks[1], (m,), 0, npg, I32)
        return dict(
            features=jax.random.normal(ks[2], (n, cfg.d_feat), F32),
            edge_src=src + edge_off,
            edge_dst=dst + edge_off,
            edge_mask=jnp.ones((m,), F32),
            graph_ids=gid,
            graph_labels=jax.random.randint(ks[2], (bsz,), 0, cfg.n_classes, I32),
        )

    dims = [cfg.d_feat] + [cfg.d_hidden] * (cfg.n_layers - 1) + [cfg.n_classes]
    flops = 3.0 * sum(
        2.0 * m * dims[i] + 2.0 * n * dims[i] * dims[i + 1]
        for i in range(cfg.n_layers)
    )
    return StepBundle(arch.id, shape.name, "train", init_fn, step, spec,
                      make_batch, model_flops_per_step=flops,
                      opt_cfg=opt_cfg)


# ---------------------------------------------------------------------------
# RecSys family
# ---------------------------------------------------------------------------

_REC_MODS = {"dcn": dcn, "dlrm": dlrm, "sasrec": sasrec, "mind": mind}


def _reduce_rec_shape(shape: ShapeSpec) -> ShapeSpec:
    if shape.kind == "rec_retrieval":
        return dataclasses.replace(
            shape, extra=dict(n_candidates=256), global_batch=1
        )
    return dataclasses.replace(shape, global_batch=32)


def _rec_batch_spec(kind_model: str, cfg, b: int, with_label: bool) -> dict:
    if kind_model in ("dcn", "dlrm"):
        spec = dict(dense=_sds((b, cfg.n_dense), F32),
                    sparse_ids=_sds((b, cfg.n_sparse), I32))
    elif kind_model == "sasrec":
        spec = dict(item_seq=_sds((b, cfg.seq_len), I32))
        if with_label:
            spec.update(pos=_sds((b, cfg.seq_len), I32),
                        neg=_sds((b, cfg.seq_len), I32),
                        mask=_sds((b, cfg.seq_len), F32))
    else:  # mind
        spec = dict(hist=_sds((b, cfg.hist_len), I32),
                    hist_mask=_sds((b, cfg.hist_len), F32))
        if with_label:
            spec.update(target=_sds((b,), I32),
                        neg=_sds((b, cfg.n_negatives), I32))
    if with_label and kind_model in ("dcn", "dlrm"):
        spec["label"] = _sds((b,), F32)
    return spec


def _rec_make_batch(kind_model: str, cfg, b: int, with_label: bool):
    def make_batch(key):
        ks = jax.random.split(key, 4)
        if kind_model in ("dcn", "dlrm"):
            out = dict(
                dense=jax.random.normal(ks[0], (b, cfg.n_dense), F32),
                sparse_ids=jax.random.randint(
                    ks[1], (b, cfg.n_sparse), 0, cfg.vocab_per_field, I32),
            )
            if with_label:
                out["label"] = jax.random.bernoulli(ks[2], 0.3, (b,)).astype(F32)
        elif kind_model == "sasrec":
            out = dict(item_seq=jax.random.randint(
                ks[0], (b, cfg.seq_len), 0, cfg.n_items, I32))
            if with_label:
                out.update(
                    pos=jax.random.randint(ks[1], (b, cfg.seq_len), 0,
                                           cfg.n_items, I32),
                    neg=jax.random.randint(ks[2], (b, cfg.seq_len), 0,
                                           cfg.n_items, I32),
                    mask=jnp.ones((b, cfg.seq_len), F32),
                )
        else:
            out = dict(
                hist=jax.random.randint(ks[0], (b, cfg.hist_len), 0,
                                        cfg.n_items, I32),
                hist_mask=jnp.ones((b, cfg.hist_len), F32),
            )
            if with_label:
                out["target"] = jax.random.randint(ks[1], (b,), 0,
                                                   cfg.n_items, I32)
                out["neg"] = jax.random.randint(
                    ks[2], (b, cfg.n_negatives), 0, cfg.n_items, I32)
        return out
    return make_batch


def _rec_dense_flops(kind_model: str, cfg, b: int) -> float:
    """Dense-compute model FLOPs per example (excl. embedding gathers)."""
    if kind_model == "dcn":
        d = cfg.x0_dim
        cross = cfg.n_cross_layers * 2 * d * d
        dims = [d] + list(cfg.mlp)
        deep = sum(2 * a * o for a, o in zip(dims[:-1], dims[1:]))
        return b * float(cross + deep)
    if kind_model == "dlrm":
        bot = sum(2 * a * o for a, o in
                  zip(cfg.bot_mlp[:-1], cfg.bot_mlp[1:]))
        dims = [cfg.top_in] + list(cfg.top_mlp)
        top = sum(2 * a * o for a, o in zip(dims[:-1], dims[1:]))
        inter = 2 * cfg.n_vectors ** 2 * cfg.embed_dim
        return b * float(bot + top + inter)
    if kind_model == "sasrec":
        d = cfg.embed_dim
        per_block = 8 * d * d * cfg.seq_len + 4 * d * cfg.d_ff * cfg.seq_len \
            + 4 * cfg.seq_len ** 2 * d
        return b * float(cfg.n_blocks * per_block)
    d = cfg.embed_dim
    routing = cfg.capsule_iters * 4 * cfg.hist_len * cfg.n_interests * d
    return b * float(2 * cfg.hist_len * d * d + routing)


def _rec_bundle(arch: ArchSpec, shape: ShapeSpec, cfg,
                opt_cfg: AdamWConfig) -> StepBundle:
    mod = _REC_MODS[arch.model_kind]
    init_fn = lambda key: mod.init(cfg, key)
    b = shape.global_batch

    if shape.kind == "rec_train":
        spec = _rec_batch_spec(arch.model_kind, cfg, b, with_label=True)
        step = train_loop.make_train_step(
            functools.partial(mod.loss_fn, cfg), opt_cfg
        )
        flops = 3.0 * _rec_dense_flops(arch.model_kind, cfg, b)
        return StepBundle(arch.id, shape.name, "train", init_fn, step, spec,
                          _rec_make_batch(arch.model_kind, cfg, b, True),
                          model_flops_per_step=flops, opt_cfg=opt_cfg)

    if shape.kind == "rec_serve":
        spec = _rec_batch_spec(arch.model_kind, cfg, b, with_label=False)

        def serve(params, batch):
            if arch.model_kind in ("dcn", "dlrm"):
                return mod.forward(cfg, params, batch)
            if arch.model_kind == "sasrec":
                return sasrec.user_embedding(cfg, params, batch["item_seq"])
            return mind.user_interests(cfg, params, batch["hist"],
                                       batch["hist_mask"])

        flops = _rec_dense_flops(arch.model_kind, cfg, b)
        return StepBundle(arch.id, shape.name, "serve", init_fn, serve, spec,
                          _rec_make_batch(arch.model_kind, cfg, b, False),
                          model_flops_per_step=flops)

    # retrieval: 1 user x n_candidates
    nc = shape.extra["n_candidates"]
    spec = _rec_batch_spec(arch.model_kind, cfg, 1, with_label=False)
    spec["candidates"] = _sds((nc,), I32)

    def retrieve(params, batch):
        return mod.retrieval_scores(cfg, params, batch)

    base_make = _rec_make_batch(arch.model_kind, cfg, 1, False)

    def make_batch(key):
        out = base_make(key)
        vocab = getattr(cfg, "n_items", getattr(cfg, "vocab_per_field", 1000))
        out["candidates"] = jax.random.randint(key, (nc,), 0, vocab, I32)
        return out

    if arch.model_kind in ("dcn", "dlrm"):
        flops = _rec_dense_flops(arch.model_kind, cfg, nc)
    else:
        flops = 2.0 * nc * cfg.embed_dim
    return StepBundle(arch.id, shape.name, "serve", init_fn, retrieve, spec,
                      make_batch, model_flops_per_step=flops)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def reduce_shape(arch: ArchSpec, shape: ShapeSpec) -> ShapeSpec:
    if arch.family == "lm":
        return _reduce_lm_shape(shape)
    if arch.family == "gnn":
        return _reduce_gnn_shape(shape)
    return _reduce_rec_shape(shape)


def build(arch: ArchSpec, shape_name: str, *, reduced: bool = False,
          opt_cfg: Optional[AdamWConfig] = None,
          config_overrides: Optional[Dict[str, Any]] = None) -> StepBundle:
    """Build the StepBundle for one cell.

    ``reduced=True`` swaps in the smoke config *and* the reduced shape —
    this is what the per-arch smoke tests and CPU examples run.
    ``config_overrides`` does a dataclasses.replace on the model config
    (the dry-run injects activation-sharding hints here).
    """
    shape = arch.shape(shape_name)
    cfg = arch.reduced if reduced else arch.config
    if reduced:
        shape = reduce_shape(arch, shape)
    if config_overrides:
        cfg = dataclasses.replace(cfg, **config_overrides)
    opt = opt_cfg or (SMOKE_OPT if reduced else DEFAULT_OPT)
    if arch.family == "lm":
        return _lm_bundle(arch, shape, cfg, opt)
    if arch.family == "gnn":
        return _gnn_bundle(arch, shape, cfg, opt, reduced)
    return _rec_bundle(arch, shape, cfg, opt)
