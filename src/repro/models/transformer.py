"""Unified model: wires attention/MLP/MoE/Mamba2/xLSTM blocks per ModelConfig.

Parameters are plain nested dicts; repeated layers are stacked on a leading
"scan" axis and traversed with lax.scan so HLO size stays O(1) in depth.
Forward modes:
  * "train"/"encode": full-sequence logits (b, s, vocab)
  * "prefill": last-position logits + initialized caches
  * "decode": one-token logits + updated caches (serve_step body)
  * "chunk": chunked-prefill continuation over paged caches, last-valid logits
  * "verify": speculative draft verification over paged caches, full logits
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba2 as m2
from repro.models import xlstm as xl
from repro.models.layers import (Initializer, apply_mlp, apply_norm, init_mlp,
                                 init_norm, softcap)
from repro.models.moe import apply_moe, init_moe
from repro.models.sharding import ShardingRules, constrain


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _ssm_layout(cfg: ModelConfig):
    """(n_groups, mlstm_per_group, n_slstm). slstm_every == 0 => pure mLSTM
    (used by the dry-run's shallow cost probes)."""
    if not cfg.xlstm.slstm_every:
        return 1, cfg.num_layers, 0
    n_groups = cfg.num_layers // cfg.xlstm.slstm_every
    return n_groups, cfg.xlstm.slstm_every - 1, n_groups


def _init_block(init: Initializer, prefix: str, cfg: ModelConfig, moe_layer: bool):
    p = {
        "ln1": init_norm(init, f"{prefix}.ln1", cfg, cfg.d_model),
        "attn": attn.init_attention(init, f"{prefix}.attn", cfg),
        "ln2": init_norm(init, f"{prefix}.ln2", cfg, cfg.d_model),
    }
    if moe_layer:
        p["moe"] = init_moe(init, f"{prefix}.moe", cfg)
    else:
        p["mlp"] = init_mlp(init, f"{prefix}.mlp", cfg)
    return p


def _stacked(cfg: ModelConfig, key, build, n: int, zero_out: bool = True):
    """Stack ``n`` copies of ``build(init)`` on a leading scan axis; returns
    (params, flat-axes-with-scan-prefix)."""
    axes = {}
    trees = []
    for i in range(n):
        ini = Initializer(cfg, jax.random.fold_in(key, i), zero_out)
        trees.append(build(ini))
        axes = ini.axes
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *trees)
    axes = {k: ("scan",) + tuple(v) for k, v in axes.items()}
    return stacked, axes


def init_model(cfg: ModelConfig, key,
               zero_out: bool = True) -> Tuple[Dict, Dict[str, tuple]]:
    """Returns (params, flat axes dict path->logical axes).

    ``zero_out`` is the training init: attention, MLP and expert output
    projections start at zero, so every block is the identity and the
    logits depend on the last token only. Serving random weights passes
    ``zero_out=False`` so attention and the KV cache reach the logits."""
    init = Initializer(cfg, jax.random.fold_in(key, 0xE0), zero_out)
    flat_axes: Dict[str, tuple] = {}
    params: Dict = {}

    d = cfg.d_model
    # N(0, 1/d) embeddings + sqrt(d) input scaling (gemma-style): keeps the
    # residual stream ~unit variance AND tied-head logits ~unit variance.
    params["embed"] = init.w("embed", (cfg.vocab_size, d), ("vocab", "w_embed"),
                             scale=d ** -0.5)
    if cfg.stub_frontend:
        params["frontend_proj"] = init.w("frontend_proj", (cfg.frontend_dim, d),
                                         (None, "w_embed"))
    params["final_norm"] = init_norm(init, "final_norm", cfg, d)
    if not cfg.tie_embeddings:
        params["head"] = init.w("head", (d, cfg.vocab_size), ("w_embed", "vocab"),
                                scale=d ** -0.5)
    flat_axes.update(init.axes)

    if cfg.family in ("dense", "vlm", "audio"):
        params["layers"], ax = _stacked(
            cfg, jax.random.fold_in(key, 1),
            lambda ini: _init_block(ini, "layers", cfg, False), cfg.num_layers,
            zero_out)
        flat_axes.update(ax)
    elif cfg.family == "moe":
        kd = cfg.moe.first_k_dense
        params["dense_layers"], ax = _stacked(
            cfg, jax.random.fold_in(key, 1),
            lambda ini: _init_block(ini, "dense_layers", cfg, False), kd,
            zero_out)
        flat_axes.update(ax)
        params["layers"], ax = _stacked(
            cfg, jax.random.fold_in(key, 2),
            lambda ini: _init_block(ini, "layers", cfg, True),
            cfg.num_layers - kd, zero_out)
        flat_axes.update(ax)
    elif cfg.family == "hybrid":
        params["mamba"], ax = _stacked(
            cfg, jax.random.fold_in(key, 1),
            lambda ini: m2.init_mamba2(ini, "mamba", cfg), cfg.num_layers)
        flat_axes.update(ax)
        ini = Initializer(cfg, jax.random.fold_in(key, 2), zero_out)
        params["shared"] = _init_block(ini, "shared", cfg, False)
        flat_axes.update(ini.axes)
    elif cfg.family == "ssm":
        n_groups, n_m_per, n_slstm = _ssm_layout(cfg)
        params["mlstm"], ax = _stacked(
            cfg, jax.random.fold_in(key, 1),
            lambda ini: xl.init_mlstm(ini, "mlstm", cfg), n_groups * n_m_per)
        flat_axes.update(ax)
        if n_slstm:
            params["slstm"], ax = _stacked(
                cfg, jax.random.fold_in(key, 2),
                lambda ini: xl.init_slstm(ini, "slstm", cfg), n_slstm)
            flat_axes.update(ax)
    else:
        raise ValueError(cfg.family)
    return params, flat_axes


@functools.partial(jax.jit, static_argnums=(0, 2))
def init_params(cfg: ModelConfig, key, zero_out: bool = True):
    """``init_model``'s params built by one compiled program (bitwise equal
    to the eager build). At published width the eager build holds every
    per-layer tree, its f32 temporaries and the stacked copy in device
    memory at once; one program lets XLA write the stacked arrays directly."""
    return init_model(cfg, key, zero_out)[0]


def axes_tree(params, flat_axes):
    """Nested axes tree mirroring the params structure."""
    def lookup(kp, _leaf):
        path = ".".join(str(k.key) for k in kp)
        return tuple(flat_axes[path])
    return jax.tree_util.tree_map_with_path(lookup, params)


def abstract_model(cfg: ModelConfig, key=None):
    key = jax.random.PRNGKey(0) if key is None else key
    flat_holder = {}

    def go(k):
        p, ax = init_model(cfg, k)
        flat_holder.update(ax)
        return p

    params = jax.eval_shape(go, key)
    return params, flat_holder


# ---------------------------------------------------------------------------
# blocks (apply)
# ---------------------------------------------------------------------------

def _block_fwd(p, x, positions, cfg: ModelConfig, mode: str, cache, rules,
               moe_layer: bool, mesh=None, q_valid=None):
    """Standard (attention + mlp/moe) block. Returns (x, new_cache, aux)."""
    h = apply_norm(p["ln1"], x, cfg)
    if (cfg.attn_in_seqshard and rules is not None
            and mode not in ("decode", "chunk")
            and cfg.num_heads % rules.axis_sizes.get("model", 1) != 0):
        # enter sequence-parallel attention at d_model width (cheap) instead
        # of resharding the nh*hd-wide Q tensor inside attention
        from repro.models.sharding import constrain as _constrain
        h = _constrain(h, rules, ("batch", "attn_qseq", "embed"))
    # named scopes: the compiled program's op_name metadata says which
    # part of the block each device op belongs to (bench/enginetrace.py)
    with jax.named_scope("attention"):
        if mode == "decode":
            if cfg.attn_type == "mla":
                a, new_cache = attn.mla_decode(p["attn"], h, cfg, cache)
            else:
                a, new_cache = attn.gqa_decode(p["attn"], h, cfg, cache)
        elif mode == "chunk":
            if cfg.attn_type == "mla":
                raise NotImplementedError("chunked prefill supports "
                                          "gqa-family attention only "
                                          "(paged KV)")
            a, new_cache = attn.gqa_prefill_paged(p["attn"], h, cfg, cache,
                                                  q_valid)
        elif mode == "verify":
            if cfg.attn_type == "mla":
                raise NotImplementedError("speculative verify supports "
                                          "gqa-family attention only "
                                          "(paged KV)")
            a, new_cache = attn.gqa_verify_paged(p["attn"], h, cfg, cache,
                                                 q_valid)
        else:
            if cfg.attn_type == "mla":
                a, new_cache = attn.mla_prefill(p["attn"], h, positions, cfg,
                                                cache, rules=rules)
            else:
                a, new_cache = attn.gqa_prefill(p["attn"], h, positions, cfg,
                                                cache, rules=rules)
    x = x + a
    h = apply_norm(p["ln2"], x, cfg)
    aux = jnp.zeros((), jnp.float32)
    if moe_layer:
        with jax.named_scope("moe"):
            mo, aux = apply_moe(p["moe"], h, cfg, mesh)
        x = x + mo
    else:
        with jax.named_scope("mlp"):
            x = x + apply_mlp(p["mlp"], h, cfg)
    if rules is not None:
        x = constrain(x, rules, ("batch", "seq", "embed"))
    return x, new_cache, aux


def _remat(fn, cfg: ModelConfig, mode: str):
    if mode != "train" or cfg.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.nothing_saveable if cfg.remat == "full"
              else jax.checkpoint_policies.dots_saveable)
    return jax.checkpoint(fn, policy=policy)


def _maybe_scan(body, init, xs, scan: bool):
    """lax.scan, or a python unroll when ``scan`` is False.

    The unrolled form is used by the dry-run: XLA's cost_analysis counts a
    while-loop body ONCE regardless of trip count, so roofline terms from a
    scanned model would be ~L x too small (verified empirically).
    """
    if scan:
        return jax.lax.scan(body, init, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    carry = init
    ys = []
    for i in range(n):
        carry, y = body(carry, jax.tree.map(lambda t: t[i], xs))
        ys.append(y)
    stacked = jax.tree.map(lambda *zs: jnp.stack(zs, 0), *ys)
    return carry, stacked


def _scan_blocks(params_stack, x, positions, cfg, mode, caches, rules,
                 moe_layer, mesh, q_valid=None):
    """lax.scan over stacked blocks; caches (optional) are stacked on the
    same leading axis."""
    has_cache = caches is not None

    def body(carry, xs):
        x, aux = carry
        if has_cache:
            p, cache = xs
        else:
            p, cache = xs, None
        x, new_cache, a = _block_fwd(p, x, positions, cfg, mode, cache, rules,
                                     moe_layer, mesh, q_valid=q_valid)
        if not has_cache:
            new_cache = jnp.zeros((), jnp.int32)
        return (x, aux + a), new_cache

    body = _remat(body, cfg, mode)
    xs = (params_stack, caches) if has_cache else params_stack
    (x, aux), new_caches = _maybe_scan(body, (x, jnp.zeros((), jnp.float32)),
                                       xs, cfg.scan_layers)
    return x, (new_caches if has_cache else None), aux


def _no_cache(n: int):
    return None


# ---------------------------------------------------------------------------
# full forward
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, *, tokens=None, embeds=None,
            mode: str = "train", caches=None, rules: Optional[ShardingRules] = None,
            mesh=None, q_valid=None):
    """Returns (logits, new_caches, aux_loss).

    mode="chunk" is the chunked-prefill pass: ``tokens`` (b, s) holds one
    left-aligned chunk per row, ``q_valid`` (b,) its per-row valid token
    count, and ``caches`` must be paged — each chunk continues from the
    request's cached context at position ``cache["length"]``. Logits are
    taken at each row's LAST VALID chunk position (the whole-prefill
    analogue of "last position"); rows with ``q_valid == 0`` produce
    garbage logits the caller ignores.

    mode="verify" is the speculative draft-and-verify pass: like "chunk" the
    ``tokens`` row is a left-aligned continuation (last committed token +
    draft tokens, ``q_valid`` valid per row) written through the paged
    cache, but the logits come back UN-sliced — ``(b, s, vocab)`` — because
    acceptance needs the argmax at *every* draft position, and position j's
    logits are bit-identical to what sequential one-token decode would
    produce there.
    """
    compute = jnp.dtype(cfg.compute_dtype)
    if embeds is not None:
        x = embeds.astype(compute) @ params["frontend_proj"].astype(compute)
        b, s = embeds.shape[:2]
    else:
        x = params["embed"].astype(compute)[tokens]
        x = x * jnp.asarray(cfg.d_model ** 0.5, compute)
        b, s = tokens.shape
    if mode in ("decode", "chunk", "verify"):
        positions = None  # per-request positions come from cache lengths
    else:
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
    if rules is not None:
        x = constrain(x, rules, ("batch", "seq", "embed"))

    aux_total = jnp.zeros((), jnp.float32)
    new_caches = {}

    if cfg.family in ("dense", "vlm", "audio"):
        c = caches["attn"] if caches is not None else None
        x, nc, aux = _scan_blocks(params["layers"], x, positions, cfg, mode,
                                  c, rules, False, mesh, q_valid=q_valid)
        new_caches = None if caches is None else {"attn": nc}
        aux_total += aux

    elif cfg.family == "moe":
        cd = caches["dense_attn"] if caches is not None else None
        cm = caches["attn"] if caches is not None else None
        x, ncd, aux1 = _scan_blocks(params["dense_layers"], x, positions, cfg,
                                    mode, cd, rules, False, mesh,
                                    q_valid=q_valid)
        x, ncm, aux2 = _scan_blocks(params["layers"], x, positions, cfg, mode,
                                    cm, rules, True, mesh, q_valid=q_valid)
        aux_total += aux1 + aux2
        new_caches = (None if caches is None else {"dense_attn": ncd, "attn": ncm})

    elif cfg.family == "hybrid":
        n_apps = (cfg.num_layers // cfg.shared_attn_every
                  if cfg.shared_attn_every else 0)
        per = cfg.shared_attn_every or (cfg.num_layers + 1)
        mstate = caches["mamba"] if caches is not None else None
        attn_c = caches.get("attn") if caches is not None else None
        want_state = caches is not None
        new_mstate, new_attn_c = [], []

        def mamba_span(lo, hi, x, mstate_slice):
            p_slice = jax.tree.map(lambda t: t[lo:hi], params["mamba"])
            if mode == "decode":
                def body(xc, xs):
                    p, st = xs
                    y, new_st = m2.mamba2_decode(p, xc, cfg, st)
                    return xc + y, new_st
                x, new_st = _maybe_scan(body, x, (p_slice, mstate_slice),
                                        cfg.scan_layers)
                return x, new_st
            def body(xc, p):
                y, st = m2.mamba2_forward(p, xc, cfg, return_state=want_state)
                if not want_state:
                    st = jnp.zeros((), jnp.int32)
                return xc + y, st
            body = _remat(body, cfg, mode)
            x, sts = _maybe_scan(body, x, p_slice, cfg.scan_layers)
            return x, sts

        idx = 0
        for g in range(n_apps):
            ms = None if mstate is None else jax.tree.map(
                lambda t: t[idx:idx + per], mstate)
            x, st = mamba_span(idx, idx + per, x, ms)
            if want_state:
                new_mstate.append(st)
            ac = None if attn_c is None else jax.tree.map(lambda t: t[g], attn_c)
            x, nac, _ = _block_fwd(params["shared"], x, positions, cfg, mode,
                                   ac, rules, False, mesh)
            if want_state and nac is not None:
                new_attn_c.append(nac)
            idx += per
        if idx < cfg.num_layers:
            ms = None if mstate is None else jax.tree.map(
                lambda t: t[idx:], mstate)
            x, st = mamba_span(idx, cfg.num_layers, x, ms)
            if want_state:
                new_mstate.append(st)
        if want_state:
            new_caches = {
                "mamba": jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *new_mstate),
            }
            if new_attn_c:
                new_caches["attn"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs, 0), *new_attn_c)
        else:
            new_caches = None

    elif cfg.family == "ssm":
        n_groups, n_m_per, n_slstm = _ssm_layout(cfg)
        want_state = caches is not None
        mstate = caches["mlstm"] if caches is not None else None
        sstate = caches.get("slstm") if caches is not None else None
        new_m, new_s = [], []
        for g in range(n_groups):
            lo = g * n_m_per
            p_slice = jax.tree.map(lambda t: t[lo:lo + n_m_per], params["mlstm"])
            ms = None if mstate is None else jax.tree.map(
                lambda t: t[lo:lo + n_m_per], mstate)
            if mode == "decode":
                def body(xc, xs):
                    p, st = xs
                    y, new_st = xl.mlstm_decode(p, xc, cfg, st)
                    return xc + y, new_st
                x, sts = _maybe_scan(body, x, (p_slice, ms), cfg.scan_layers)
            else:
                def body(xc, p):
                    # NOTE: mLSTM chunk scan stays a lax.scan even in the
                    # dry-run's unrolled probes — its in-scan intra-chunk cost
                    # is ~3% of the block (see EXPERIMENTS caveats); unrolling
                    # 128 chunk bodies makes SPMD compile time explode.
                    y, st = xl.mlstm_forward(p, xc, cfg,
                                             return_state=want_state,
                                             unroll_chunks=False)
                    if not want_state:
                        st = jnp.zeros((), jnp.int32)
                    return xc + y, st
                body = _remat(body, cfg, mode)
                x, sts = _maybe_scan(body, x, p_slice, cfg.scan_layers)
            if want_state:
                new_m.append(sts)
            if n_slstm:
                sp = jax.tree.map(lambda t: t[g], params["slstm"])
                ss = None if sstate is None else jax.tree.map(
                    lambda t: t[g], sstate)
                y, new_ss = xl.slstm_forward(sp, x, cfg, state=ss,
                                             return_state=want_state)
                x = x + y
                if want_state:
                    new_s.append(new_ss)
        if want_state:
            new_caches = {
                "mlstm": jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *new_m),
            }
            if new_s:
                new_caches["slstm"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs, 0), *new_s)
        else:
            new_caches = None
    else:
        raise ValueError(cfg.family)

    x = apply_norm(params["final_norm"], x, cfg)
    head = (params["embed"].T if cfg.tie_embeddings else params["head"])
    if mode in ("prefill",):
        x = x[:, -1:, :]
    elif mode == "chunk":
        # per-row last VALID chunk position (q_valid == 0 rows read position
        # 0 and produce garbage the caller ignores)
        idx = jnp.maximum(q_valid - 1, 0).astype(jnp.int32)[:, None, None]
        x = jnp.take_along_axis(x, idx, axis=1)
    with jax.named_scope("lm_head"):
        logits = (x @ head.astype(x.dtype)).astype(
            jnp.dtype(cfg.logits_dtype))
        logits = softcap(logits, cfg.logits_softcap)
    if mode in ("prefill", "decode", "chunk"):
        logits = logits[:, -1, :]
    return logits, new_caches, aux_total


# ---------------------------------------------------------------------------
# cache factories
# ---------------------------------------------------------------------------

def init_cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    """Abstract cache pytree (ShapeDtypeStructs) + matching logical axes."""
    def stack_spec(spec, n):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n, *s.shape), s.dtype), spec)

    def stack_axes(ax, extra=("scan",)):
        return jax.tree.map(lambda a: tuple(extra) + tuple(a), ax,
                            is_leaf=lambda x: isinstance(x, tuple) and all(
                                isinstance(e, (str, type(None))) for e in x))

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        base = attn.cache_spec(cfg, batch, max_len)
        ax = attn.cache_axes(cfg)
        if cfg.family == "moe":
            kd = cfg.moe.first_k_dense
            spec = {"dense_attn": stack_spec(base, kd),
                    "attn": stack_spec(base, cfg.num_layers - kd)}
            axes = {"dense_attn": stack_axes(ax), "attn": stack_axes(ax)}
        else:
            spec = {"attn": stack_spec(base, cfg.num_layers)}
            axes = {"attn": stack_axes(ax)}
        return spec, axes
    if cfg.family == "hybrid":
        n_apps = (cfg.num_layers // cfg.shared_attn_every
                  if cfg.shared_attn_every else 0)
        spec = {"mamba": stack_spec(m2.mamba2_state_spec(cfg, batch), cfg.num_layers)}
        axes = {"mamba": stack_axes(m2.mamba2_state_axes())}
        if n_apps:
            spec["attn"] = stack_spec(attn.cache_spec(cfg, batch, max_len), n_apps)
            axes["attn"] = stack_axes(attn.cache_axes(cfg))
        return spec, axes
    if cfg.family == "ssm":
        n_groups, n_m_per, n_slstm = _ssm_layout(cfg)
        spec = {"mlstm": stack_spec(xl.mlstm_state_spec(cfg, batch),
                                    n_groups * n_m_per)}
        axes = {"mlstm": stack_axes(xl.mlstm_state_axes())}
        if n_slstm:
            spec["slstm"] = stack_spec(xl.slstm_state_spec(cfg, batch), n_slstm)
            axes["slstm"] = stack_axes(xl.slstm_state_axes())
        return spec, axes
    raise ValueError(cfg.family)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    spec, _ = init_cache_spec(cfg, batch, max_len)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)


def init_paged_cache(cfg: ModelConfig, batch: int, num_blocks: int,
                     block_tokens: int, max_blocks: int):
    """Paged-cache pytree for the real-execution engine: same layer grouping
    as ``init_cache`` (``attn`` / ``dense_attn`` stacks on a leading scan
    axis) but each layer holds a pooled page array plus per-request block
    tables instead of a contiguous ``(b, S)`` cache.

    The pool gets ``num_blocks + 1`` physical pages: page ``num_blocks`` is
    the engine's *trash page* — dead batch rows' tables point at it (every
    block-table entry must be a valid pool index for the gather), and it is
    where their masked decode writes land. Block tables start all-trash and
    lengths at 0. Only attention-cache families page; recurrent state
    (hybrid/ssm) has no pages to share."""
    if cfg.family not in ("dense", "vlm", "audio", "moe"):
        raise NotImplementedError(
            f"paged KV cache is attention-only (family={cfg.family})")
    trash = num_blocks

    def stack(n):
        base = attn.paged_cache_spec(cfg, num_blocks + 1, block_tokens,
                                     batch, max_blocks)
        one = {k: (jnp.full(s.shape, trash, jnp.int32)
                   if k == "block_tables" else jnp.zeros(s.shape, s.dtype))
               for k, s in base.items()}
        return jax.tree.map(lambda t: jnp.stack([t] * n, 0), one)

    if cfg.family == "moe":
        kd = cfg.moe.first_k_dense
        return {"dense_attn": stack(kd), "attn": stack(cfg.num_layers - kd)}
    return {"attn": stack(cfg.num_layers)}
