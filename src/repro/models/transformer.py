"""Decoder-only transformer LM: GQA + RoPE + SwiGLU, optional MoE.

Design notes (pod-scale discipline):

* **scan over layers**: params are stacked with a leading ``n_layers`` dim
  and the stack is applied with ``lax.scan`` -> HLO size is O(1) in depth,
  which keeps 64-layer × 512-device lowering tractable and makes remat
  policy uniform.
* **remat**: each layer body is ``jax.checkpoint``-ed (save boundaries,
  recompute interior) when ``cfg.remat``.
* **chunked loss**: logits for a [B, S, V] block can dominate peak memory
  (command-r: V=256k); ``loss_chunk`` computes CE per sequence chunk inside
  a scan.
* **MoE**: capacity-based dispatch via sort + scatter (static shapes, no
  [T, E, C] one-hots).  When ``n_experts`` < the model-axis size, experts
  are *split* into ``ep_split`` virtual experts along the SwiGLU ff dim
  (exactly tensor-parallelism inside each expert) so the expert dim always
  matches the mesh — grok's 8 experts become 16 virtual experts on a
  16-way axis.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models.attention import chunked_attention, decode_attention


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    ep_split: int = 1          # virtual experts per expert (ff-dim split)
    aux_loss_weight: float = 0.01


@dataclasses.dataclass(frozen=True)
class ActSharding:
    """Activation-sharding hints (mesh axis names), applied via
    with_sharding_constraint when lowering under a mesh.  ``None`` (the
    default on the config) keeps the model mesh-agnostic for CPU tests.

    ``mesh`` (a concrete jax Mesh) additionally enables the shard_map MoE
    dispatch path: local per-data-shard routing + FSDP weight all-gather +
    psum combine.  Without it, GSPMD lowers the global scatter dispatch to
    full-capacity-buffer all-reduces (measured 60 TB/step on grok).
    ``fsdp_axis`` is the axis expert weights' d-dim is sharded over.
    """

    batch: Tuple[str, ...] = ("data",)
    model: str = "model"
    mesh: Any = None
    fsdp_axis: str = "data"
    # Megatron-style sequence parallelism: the residual stream (and thus
    # every remat boundary the backward pass stores) is sharded over the
    # model axis along seq.  Costs one all-gather + reduce-scatter pair per
    # layer; divides boundary-activation HBM by the model-axis size.
    seq_shard: bool = True


def _constrain(x: jax.Array, spec) -> jax.Array:
    """with_sharding_constraint that is a no-op outside a mesh context."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError, TypeError):
        return x


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    moe: Optional[MoEConfig] = None
    rope_theta: float = 10000.0
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attn_chunk: int = 1024
    loss_chunk: int = 0        # 0 = unchunked
    remat: bool = True
    act_shard: Optional[ActSharding] = None
    # Pre-cast params to compute dtype once per step, *before* any FSDP
    # all-gather: the convert runs on the local shard, so gathers move bf16
    # instead of fp32 — halves FSDP wire bytes (§Perf command-r iteration).
    precast_params: bool = False
    # int8 KV cache (per-token, per-head dynamic scales): halves-to-quarters
    # decode HBM; required for MHA archs (qwen kv=40) at 32k+ contexts.
    kv_quant: bool = False

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def param_count(self) -> int:
        d, ff, v = self.d_model, self.d_ff, self.vocab
        attn = d * self.n_heads * self.hd * 2 + d * self.n_kv_heads * self.hd * 2
        if self.moe:
            ffn = self.moe.n_experts * (2 * d * ff + ff * d) + d * self.moe.n_experts
        else:
            ffn = 2 * d * ff + ff * d
        per_layer = attn + ffn + 2 * d
        return self.n_layers * per_layer + 2 * v * d + d

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k of n_experts)."""
        if not self.moe:
            return self.param_count()
        d, ff = self.d_model, self.d_ff
        full_ffn = self.moe.n_experts * 3 * d * ff
        active_ffn = self.moe.top_k * 3 * d * ff
        return self.param_count() - self.n_layers * (full_ffn - active_ffn)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(cfg: TransformerConfig, key) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    d, hd = cfg.d_model, cfg.hd
    dt = cfg.param_dtype
    p: Dict[str, Any] = {
        "ln_attn": L.rmsnorm_init(d, dt),
        "ln_ffn": L.rmsnorm_init(d, dt),
        "wq": L.dense_init(ks[0], d, cfg.n_heads * hd, bias=cfg.qkv_bias, dtype=dt),
        "wk": L.dense_init(ks[1], d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dt),
        "wv": L.dense_init(ks[2], d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dt),
        "wo": L.dense_init(ks[3], cfg.n_heads * hd, d, dtype=dt),
    }
    if cfg.moe:
        e = cfg.moe.n_experts * cfg.moe.ep_split
        ffs = cfg.d_ff // cfg.moe.ep_split
        def ew(key, a, b):
            return (jax.random.normal(key, (e, a, b), jnp.float32)
                    * (a ** -0.5)).astype(dt)
        p["router"] = L.dense_init(ks[4], d, cfg.moe.n_experts, dtype=jnp.float32)
        p["w_gate"] = ew(ks[5], d, ffs)
        p["w_up"] = ew(ks[6], d, ffs)
        p["w_down"] = ew(ks[7], ffs, d)
    else:
        p["w_gate"] = L.dense_init(ks[5], d, cfg.d_ff, dtype=dt)
        p["w_up"] = L.dense_init(ks[6], d, cfg.d_ff, dtype=dt)
        p["w_down"] = L.dense_init(ks[7], cfg.d_ff, d, dtype=dt)
    return p


def init(cfg: TransformerConfig, key) -> Dict[str, Any]:
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    stacked = jax.vmap(lambda k: _init_layer(cfg, k))(layer_keys)
    return {
        "embed": L.embedding_init(k_embed, cfg.vocab, cfg.d_model, cfg.param_dtype),
        "layers": stacked,
        "ln_final": L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
        "lm_head": L.dense_init(k_head, cfg.d_model, cfg.vocab, dtype=cfg.param_dtype),
    }


# ---------------------------------------------------------------------------
# MoE ffn
# ---------------------------------------------------------------------------

def _moe_ffn(cfg: TransformerConfig, p, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: [T, d] -> ([T, d], aux_loss). Capacity-based sort dispatch."""
    moe = cfg.moe
    t, d = x.shape
    e_real, k = moe.n_experts, moe.top_k
    split = moe.ep_split
    e_virt = e_real * split
    kv = k * split  # each selected expert contributes `split` virtual slots
    cap = max(int(t * kv * moe.capacity_factor / e_virt), 1)

    logits = x.astype(jnp.float32) @ p["router"]["w"]          # [T, E]
    gates = jax.nn.softmax(logits, axis=-1)
    top_g, top_e = jax.lax.top_k(gates, k)                      # [T, k]
    top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch): E * sum_e f_e * P_e
    me = jnp.mean(gates, axis=0)
    ce = jnp.zeros((e_real,), jnp.float32).at[top_e.reshape(-1)].add(
        1.0 / (t * k)
    )
    aux = moe.aux_loss_weight * e_real * jnp.sum(me * ce)

    # virtual-expert expansion: expert e -> slots e*split .. e*split+split-1
    offs = jnp.arange(split, dtype=top_e.dtype)
    flat_e = (top_e[:, :, None] * split + offs).reshape(-1)     # [T*kv]
    flat_w = jnp.broadcast_to(top_g[:, :, None], (t, k, split)).reshape(-1)
    flat_tok = jnp.broadcast_to(
        jnp.arange(t)[:, None, None], (t, k, split)
    ).reshape(-1)

    order = jnp.argsort(flat_e, stable=True)
    se, stok, sw = flat_e[order], flat_tok[order], flat_w[order]
    counts = jnp.zeros((e_virt,), jnp.int32).at[se].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(t * kv, dtype=jnp.int32) - starts[se]
    keep = pos < cap
    pos_c = jnp.minimum(pos, cap - 1)

    xt = x.astype(cfg.compute_dtype)
    buf = jnp.zeros((e_virt, cap, d), cfg.compute_dtype)
    vals = jnp.take(xt, stok, axis=0) * keep[:, None].astype(xt.dtype)
    buf = buf.at[se, pos_c].add(vals)
    if cfg.act_shard is not None:
        # expert dim over 'model' (EP) AND capacity over the data axes —
        # without the latter every data row recomputes the full expert FFN
        # (measured 16x flops blow-up on grok before this constraint).
        buf = _constrain(buf, P(cfg.act_shard.model, cfg.act_shard.batch, None))

    wg = p["w_gate"].astype(cfg.compute_dtype)
    wu = p["w_up"].astype(cfg.compute_dtype)
    wd = p["w_down"].astype(cfg.compute_dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum(
        "ecd,edf->ecf", buf, wu
    )
    out_buf = jnp.einsum("ecf,efd->ecd", h, wd)                 # [E, cap, d]
    if cfg.act_shard is not None:
        out_buf = _constrain(
            out_buf, P(cfg.act_shard.model, cfg.act_shard.batch, None)
        )

    tok_out = out_buf[se, pos_c] * (keep.astype(jnp.float32) * sw)[:, None].astype(
        out_buf.dtype
    )
    out = jnp.zeros((t, d), cfg.compute_dtype).at[stok].add(tok_out)
    return out, aux


def _moe_ffn_shardmap(cfg: TransformerConfig, p, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map: the production dispatch path.

    Layout: tokens sharded over the batch axes, virtual experts over the
    model axis, expert weights' d-dim FSDP-sharded over ``fsdp_axis``.
    Per shard: route/sort/scatter locally (zero communication), all-gather
    only *my* experts' weights over the FSDP axis, run the expert FFN on my
    experts' local slots, and psum partial token outputs over the model
    axis.  Wire cost per layer = FSDP weight gather + one activation psum —
    versus GSPMD's full-capacity-buffer all-reduces for the same math.
    """
    ash = cfg.act_shard
    mesh = ash.mesh
    moe = cfg.moe
    e_virt = moe.n_experts * moe.ep_split
    ep = int(mesh.shape[ash.model])
    assert e_virt % ep == 0, (e_virt, ep)
    e_local = e_virt // ep
    kv = moe.top_k * moe.ep_split

    def local(x_blk, rw, wg, wu, wd):
        t_l, d = x_blk.shape
        cap = max(int(t_l * kv * moe.capacity_factor / e_virt), 1)
        # --- routing (local tokens, replicated router) -------------------
        logits = x_blk.astype(jnp.float32) @ rw
        gates = jax.nn.softmax(logits, axis=-1)
        top_g, top_e = jax.lax.top_k(gates, moe.top_k)
        top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)
        me = jnp.mean(gates, axis=0)
        ce = jnp.zeros((moe.n_experts,), jnp.float32).at[
            top_e.reshape(-1)].add(1.0 / (t_l * moe.top_k))
        aux = moe.aux_loss_weight * moe.n_experts * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, ash.batch)

        # --- local dispatch (sort + positions, no comms) ------------------
        offs = jnp.arange(moe.ep_split, dtype=top_e.dtype)
        flat_e = (top_e[:, :, None] * moe.ep_split + offs).reshape(-1)
        flat_w = jnp.broadcast_to(
            top_g[:, :, None], top_g.shape + (moe.ep_split,)).reshape(-1)
        flat_tok = jnp.broadcast_to(
            jnp.arange(t_l)[:, None, None], (t_l, moe.top_k, moe.ep_split)
        ).reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        se, stok, sw = flat_e[order], flat_tok[order], flat_w[order]
        counts = jnp.zeros((e_virt,), jnp.int32).at[se].add(1)
        starts = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(t_l * kv, dtype=jnp.int32) - starts[se]
        keep = pos < cap
        pos_c = jnp.minimum(pos, cap - 1)
        xt = x_blk.astype(cfg.compute_dtype)
        buf = jnp.zeros((e_virt, cap, d), cfg.compute_dtype)
        vals = jnp.take(xt, stok, axis=0) * keep[:, None].astype(xt.dtype)
        buf = buf.at[se, pos_c].add(vals)

        # --- my experts only ----------------------------------------------
        m_idx = jax.lax.axis_index(ash.model)
        my = jax.lax.dynamic_slice_in_dim(buf, m_idx * e_local, e_local, 0)
        wg = jax.lax.all_gather(wg, ash.fsdp_axis, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, ash.fsdp_axis, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, ash.fsdp_axis, axis=2, tiled=True)
        wg = wg.astype(cfg.compute_dtype)
        wu = wu.astype(cfg.compute_dtype)
        wd = wd.astype(cfg.compute_dtype)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", my, wg)) * jnp.einsum(
            "ecd,edf->ecf", my, wu)
        out_my = jnp.einsum("ecf,efd->ecd", h, wd)          # [e_local, cap, d]

        # --- combine: partial (my experts) then psum over model -----------
        full = jnp.zeros((e_virt, cap, d), cfg.compute_dtype)
        full = jax.lax.dynamic_update_slice_in_dim(
            full, out_my, m_idx * e_local, 0)
        tok_out = full[se, pos_c] * (
            keep.astype(jnp.float32) * sw)[:, None].astype(full.dtype)
        out = jnp.zeros((t_l, d), cfg.compute_dtype).at[stok].add(tok_out)
        out = jax.lax.psum(out, ash.model)
        return out, aux

    # decode at tiny batch (long_500k: T=1) can't shard tokens over data:
    # replicate instead (redundant but negligible at 1 token).
    import numpy as _np
    dsize = int(_np.prod([mesh.shape[a] for a in ash.batch]))
    tok_axes = ash.batch if x.shape[0] % dsize == 0 and x.shape[0] >= dsize \
        else None
    in_specs = (
        P(tok_axes, None),                        # x
        P(None, None),                            # router
        P(ash.model, ash.fsdp_axis, None),        # w_gate
        P(ash.model, ash.fsdp_axis, None),        # w_up
        P(ash.model, None, ash.fsdp_axis),        # w_down
    )
    out_specs = (P(tok_axes, None), P())

    fn = jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return fn(x, p["router"]["w"], p["w_gate"], p["w_up"], p["w_down"])


def _dense_ffn(cfg: TransformerConfig, p, x: jax.Array) -> jax.Array:
    dt = cfg.compute_dtype
    g = jax.nn.silu(L.dense_apply(p["w_gate"], x, compute_dtype=dt))
    u = L.dense_apply(p["w_up"], x, compute_dtype=dt)
    return L.dense_apply(p["w_down"], g * u, compute_dtype=dt)


# ---------------------------------------------------------------------------
# layer + forward
# ---------------------------------------------------------------------------

def _attn(cfg: TransformerConfig, p, h: jax.Array, q_offset: int = 0) -> jax.Array:
    b, s, d = h.shape
    dt = cfg.compute_dtype
    hd = cfg.hd
    q = L.dense_apply(p["wq"], h, compute_dtype=dt).reshape(b, s, cfg.n_heads, hd)
    k = L.dense_apply(p["wk"], h, compute_dtype=dt).reshape(b, s, cfg.n_kv_heads, hd)
    v = L.dense_apply(p["wv"], h, compute_dtype=dt).reshape(b, s, cfg.n_kv_heads, hd)
    pos = q_offset + jnp.arange(s)
    q = L.apply_rope(q, jnp.broadcast_to(pos, (b, s)), cfg.rope_theta)
    k = L.apply_rope(k, jnp.broadcast_to(pos, (b, s)), cfg.rope_theta)
    o = chunked_attention(
        q, k, v, n_kv_heads=cfg.n_kv_heads, causal=True, chunk=cfg.attn_chunk
    )
    return L.dense_apply(p["wo"], o.reshape(b, s, cfg.n_heads * hd), compute_dtype=dt)


def _layer_body(cfg: TransformerConfig, h: jax.Array, p) -> Tuple[jax.Array, jax.Array]:
    b, s, d = h.shape
    ash = cfg.act_shard
    seq_sp = (P(ash.batch, ash.model, None)
              if ash is not None and ash.seq_shard else None)
    h = h + _attn(cfg, p, L.rmsnorm_apply(p["ln_attn"], h))
    if seq_sp is not None:
        # residual stays sequence-sharded: the TP projection's output
        # reduction becomes a reduce-scatter instead of a full all-reduce
        h = _constrain(h, seq_sp)
    x = L.rmsnorm_apply(p["ln_ffn"], h)
    if cfg.moe:
        moe_fn = (
            _moe_ffn_shardmap
            if cfg.act_shard is not None and cfg.act_shard.mesh is not None
            else _moe_ffn
        )
        y, aux = moe_fn(cfg, p, x.reshape(b * s, d))
        y = y.reshape(b, s, d)
    else:
        y, aux = _dense_ffn(cfg, p, x), jnp.zeros((), jnp.float32)
    out = h + y
    if seq_sp is not None:
        out = _constrain(out, seq_sp)
    return out, aux


def _maybe_precast(cfg: TransformerConfig, params):
    if not cfg.precast_params:
        return params
    def cast(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(cfg.compute_dtype)
        return x
    return jax.tree.map(cast, params)


def forward(cfg: TransformerConfig, params, tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """tokens [B, S] -> (hidden [B, S, d] in compute dtype, aux_loss)."""
    params = _maybe_precast(cfg, params)
    h = L.embedding_apply(params["embed"], tokens, compute_dtype=cfg.compute_dtype)
    if cfg.act_shard is not None:
        h = _constrain(h, P(cfg.act_shard.batch, None, None))

    body = functools.partial(_layer_body, cfg)
    if cfg.remat:
        body = jax.checkpoint(body)

    ash = cfg.act_shard

    def scan_fn(h, layer_params):
        if ash is not None and ash.seq_shard:
            h = _constrain(h, P(ash.batch, ash.model, None))
        h, aux = body(h, layer_params)
        return h, aux

    h, auxes = jax.lax.scan(scan_fn, h, params["layers"])
    h = L.rmsnorm_apply(params["ln_final"], h)
    return h, auxes.sum()


def loss_fn(cfg: TransformerConfig, params, batch) -> Tuple[jax.Array, dict]:
    """Next-token CE. batch: {tokens [B,S], labels [B,S], mask [B,S]}."""
    h, aux = forward(cfg, params, batch["tokens"])
    head = params["lm_head"]
    labels, mask = batch["labels"], batch["mask"]
    if cfg.loss_chunk and h.shape[1] % cfg.loss_chunk == 0:
        b, s, d = h.shape
        nc = s // cfg.loss_chunk
        hc = h.reshape(b, nc, cfg.loss_chunk, d).transpose(1, 0, 2, 3)
        lc = labels.reshape(b, nc, cfg.loss_chunk).transpose(1, 0, 2)
        mc = mask.reshape(b, nc, cfg.loss_chunk).transpose(1, 0, 2)

        # remat: without it the scan saves every chunk's logits for the
        # backward pass, recreating the full [B, S, V] buffer it exists to
        # avoid (dry-run measured 492 GB/device on smollm before this).
        @jax.checkpoint
        def chunk_nll(hx, lx, mx):
            logits = L.dense_apply(head, hx, compute_dtype=cfg.compute_dtype)
            if cfg.act_shard is not None:
                logits = _constrain(
                    logits, P(cfg.act_shard.batch, None, cfg.act_shard.model)
                )
            logits32 = logits.astype(jnp.float32)
            logz = jax.nn.logsumexp(logits32, axis=-1)
            gold = jnp.take_along_axis(logits32, lx[..., None], -1).squeeze(-1)
            return jnp.sum((logz - gold) * mx), jnp.sum(mx)

        def chunk_ce(carry, args):
            tot, cnt = carry
            t, c = chunk_nll(*args)
            return (tot + t, cnt + c), ()

        (tot, cnt), _ = jax.lax.scan(
            chunk_ce, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (hc, lc, mc),
        )
        ce = tot / jnp.maximum(cnt, 1.0)
    else:
        logits = L.dense_apply(head, h, compute_dtype=cfg.compute_dtype)
        if cfg.act_shard is not None:
            logits = _constrain(
                logits, P(cfg.act_shard.batch, None, cfg.act_shard.model)
            )
        ce = L.softmax_cross_entropy(logits, labels, mask)
    loss = ce + aux
    return loss, dict(ce=ce, aux=aux)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache(cfg: TransformerConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.hd)
    if cfg.kv_quant:
        sshape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.bfloat16),
            "v_scale": jnp.zeros(sshape, jnp.bfloat16),
            "length": jnp.zeros((), jnp.int32),
        }
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def _quantize_kv(x: jax.Array):
    """[B, 1, H, hd] -> (int8 values, bf16 per-(token,head) scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale[..., 0].astype(jnp.bfloat16)


def decode_step(cfg: TransformerConfig, params, cache, tokens: jax.Array):
    """One decode step. tokens [B, 1] -> (logits [B, 1, V], new cache).

    The cache is scanned alongside the layer stack; each layer writes its
    new K/V at position ``length``.
    """
    b = tokens.shape[0]
    dt = cfg.compute_dtype
    hd = cfg.hd
    length = cache["length"]
    h = L.embedding_apply(params["embed"], tokens, compute_dtype=dt)

    def layer(h, args):
        if cfg.kv_quant:
            p, kc, vc, ks, vs = args
        else:
            p, kc, vc = args
            ks = vs = None
        x = L.rmsnorm_apply(p["ln_attn"], h)
        q = L.dense_apply(p["wq"], x, compute_dtype=dt).reshape(b, 1, cfg.n_heads, hd)
        k = L.dense_apply(p["wk"], x, compute_dtype=dt).reshape(b, 1, cfg.n_kv_heads, hd)
        v = L.dense_apply(p["wv"], x, compute_dtype=dt).reshape(b, 1, cfg.n_kv_heads, hd)
        pos = jnp.broadcast_to(length, (b, 1))
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k = L.apply_rope(k, pos, cfg.rope_theta)
        if cfg.kv_quant:
            kq, k_sc = _quantize_kv(k)
            vq, v_sc = _quantize_kv(v)
            kc = jax.lax.dynamic_update_slice(kc, kq, (0, length, 0, 0))
            vc = jax.lax.dynamic_update_slice(vc, vq, (0, length, 0, 0))
            ks = jax.lax.dynamic_update_slice(ks, k_sc, (0, length, 0))
            vs = jax.lax.dynamic_update_slice(vs, v_sc, (0, length, 0))
            k_deq = kc.astype(dt) * ks[..., None].astype(dt)
            v_deq = vc.astype(dt) * vs[..., None].astype(dt)
        else:
            kc = jax.lax.dynamic_update_slice(
                kc, k.astype(kc.dtype), (0, length, 0, 0)
            )
            vc = jax.lax.dynamic_update_slice(
                vc, v.astype(vc.dtype), (0, length, 0, 0)
            )
            k_deq, v_deq = kc, vc
        o = decode_attention(q, k_deq, v_deq, length + 1,
                             n_kv_heads=cfg.n_kv_heads)
        h = h + L.dense_apply(
            p["wo"], o.reshape(b, 1, cfg.n_heads * hd), compute_dtype=dt
        )
        x2 = L.rmsnorm_apply(p["ln_ffn"], h)
        if cfg.moe:
            moe_fn = (
                _moe_ffn_shardmap
                if cfg.act_shard is not None and cfg.act_shard.mesh is not None
                else _moe_ffn
            )
            y, _ = moe_fn(cfg, p, x2.reshape(b, cfg.d_model))
            y = y.reshape(b, 1, cfg.d_model)
        else:
            y = _dense_ffn(cfg, p, x2)
        if cfg.kv_quant:
            return h + y, (kc, vc, ks, vs)
        return h + y, (kc, vc)

    if cfg.kv_quant:
        h, (nk, nv, nks, nvs) = jax.lax.scan(
            layer, h, (params["layers"], cache["k"], cache["v"],
                       cache["k_scale"], cache["v_scale"])
        )
        new_cache = {"k": nk, "v": nv, "k_scale": nks, "v_scale": nvs,
                     "length": length + 1}
    else:
        h, (nk, nv) = jax.lax.scan(
            layer, h, (params["layers"], cache["k"], cache["v"])
        )
        new_cache = {"k": nk, "v": nv, "length": length + 1}
    h = L.rmsnorm_apply(params["ln_final"], h)
    logits = L.dense_apply(params["lm_head"], h, compute_dtype=dt)
    return logits, new_cache
