"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract roofline terms from the compiled artifact. No device allocation —
everything flows through ShapeDtypeStructs.

MUST set XLA_FLAGS before any jax import (jax locks device count on first
init), hence the first two lines.
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import functools
import json
import re
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES_BY_NAME, applicable_shapes, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.mesh import make_production_mesh
from repro.models import steps
from repro.models import transformer as tf
from repro.models.optim import OptConfig
from repro.models.sharding import ShardingRules, tree_specs

# TPU v5e roofline constants (per chip)
PEAK_FLOPS = 197e12       # bf16
HBM_BW = 819e9            # bytes/s
ICI_BW = 50e9             # bytes/s per link

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _tensor_bytes(dtype: str, dims: str) -> float:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0.0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return float(n * b)


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum payload bytes per collective kind from HLO text. For each
    collective instruction we take the largest tensor shape on the line as
    the payload (robust to tuple-shaped async start ops)."""
    out = {k: 0.0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for kind in _COLLECTIVES:
            # match op invocation, including async -start variants; skip -done
            if (f" {kind}(" in stripped or f" {kind}-start(" in stripped):
                sizes = [_tensor_bytes(d, dims)
                         for d, dims in _SHAPE_RE.findall(stripped)]
                if sizes:
                    out[kind] += max(sizes)
                break
    return out


def wire_bytes(cb: Dict[str, float]) -> float:
    """Approximate bytes-on-the-wire: ring all-reduce moves ~2x payload,
    others ~1x."""
    return (2.0 * cb["all-reduce"] + cb["all-gather"] + cb["reduce-scatter"]
            + cb["all-to-all"] + cb["collective-permute"])


# ---------------------------------------------------------------------------

def attn_score_bytes(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Analytic GLOBAL HBM bytes of materialized attention score/prob tiles.

    XLA-CPU streams these through memory, but the TPU flash kernel keeps them
    VMEM-resident — so the honest TPU memory term subtracts them. fwd ~12
    B/elem (fp32 write + softmax pass + PV read), train ~3x for backward."""
    if cfg.attn_type == "none":
        return 0.0
    n_attn = cfg.num_layers
    if cfg.family == "hybrid":
        n_attn = cfg.num_layers // max(1, cfg.shared_attn_every)
    if shape.kind == "decode":
        elems = float(shape.global_batch) * cfg.num_heads * shape.seq_len * n_attn
        return 8.0 * elems
    causal = 0.5 if not cfg.encoder_only else 1.0
    elems = (causal * float(shape.seq_len) ** 2 * cfg.num_heads
             * shape.global_batch * n_attn)
    per_elem = 36.0 if shape.kind == "train" else 12.0
    return per_elem * elems


def _abstract_opt_state(abstract_params):
    f32 = lambda t: jax.ShapeDtypeStruct(t.shape, jnp.float32)
    return {"m": jax.tree.map(f32, abstract_params),
            "v": jax.tree.map(f32, abstract_params),
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def _sharding_tree(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               fsdp: Optional[bool] = None):
    """Returns (fn, args_abstract, in_shardings) ready for jit().lower()."""
    if fsdp is None:
        fsdp = shape.kind == "train" and cfg.param_count() > 30e9
    seq_sharded = shape.kind == "decode" and shape.global_batch == 1
    rules = ShardingRules(mesh, fsdp=fsdp, seq_sharded=seq_sharded)

    abstract_params, flat_axes = tf.abstract_model(cfg)
    p_axes = tf.axes_tree(abstract_params, flat_axes)
    p_specs = tree_specs(rules, abstract_params, p_axes)
    p_shard = _sharding_tree(mesh, p_specs)

    batch_abs = steps.input_specs(cfg, shape)
    b_axes = steps.batch_axes(cfg, shape)
    b_specs = {k: rules.spec(batch_abs[k].shape, b_axes[k]) for k in batch_abs}
    b_shard = {k: NamedSharding(mesh, b_specs[k]) for k in batch_abs}

    if shape.kind == "train":
        state_abs = {"params": abstract_params,
                     "opt": _abstract_opt_state(abstract_params)}
        opt_shard = {"m": p_shard, "v": p_shard,
                     "step": NamedSharding(mesh, P())}
        state_shard = {"params": p_shard, "opt": opt_shard}
        opt = OptConfig()
        fn = functools.partial(steps.train_step, cfg=cfg, opt=opt, rules=rules,
                               mesh=mesh)
        return fn, (state_abs, batch_abs), (state_shard, b_shard)

    if shape.kind == "prefill":
        fn = functools.partial(steps.prefill_step, cfg=cfg,
                               max_len=shape.seq_len + 8, rules=rules, mesh=mesh)
        return fn, (abstract_params, batch_abs), (p_shard, b_shard)

    # decode
    cache_abs, cache_axes = tf.init_cache_spec(cfg, shape.global_batch,
                                               shape.seq_len + 8)
    c_specs = tree_specs(rules, cache_abs, cache_axes)
    c_shard = _sharding_tree(mesh, c_specs)
    fn = functools.partial(serve_wrapper, cfg=cfg, rules=rules, mesh=mesh)
    return fn, (abstract_params, batch_abs["tokens"], cache_abs), \
        (p_shard, b_shard["tokens"], c_shard)


def serve_wrapper(params, tokens, caches, cfg, rules, mesh):
    return steps.serve_step(params, tokens, caches, cfg, rules, mesh)


def _compile_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
                  donate: bool = True, fsdp=None, donate_cache: bool = False):
    fn, args, in_sh = build_cell(cfg, shape, mesh, fsdp=fsdp)
    donate_argnums = (0,) if (donate and shape.kind == "train") else ()
    if donate_cache and shape.kind == "decode":
        donate_argnums = (2,)   # in-place KV-cache update
    with jax.set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=in_sh,
                         donate_argnums=donate_argnums)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return compiled


def _cost_of(cfg, shape, mesh, fsdp=None, donate_cache=False) -> Dict[str, float]:
    """Per-device (flops, bytes, collective wire bytes) of one UNROLLED
    compile at a reduced depth."""
    compiled = _compile_cell(cfg.replace(scan_layers=False), shape, mesh,
                             fsdp=fsdp, donate_cache=donate_cache)
    ca = compiled.cost_analysis() or {}
    cb = collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "wire": wire_bytes(cb),
            "collectives": cb}


def _axpy(base, per, n):
    out = {k: base[k] + n * per[k] for k in ("flops", "bytes", "wire")}
    out["collectives"] = {k: base["collectives"].get(k, 0.0)
                          + n * per["collectives"].get(k, 0.0)
                          for k in set(base["collectives"]) | set(per["collectives"])}
    return out


def _diff(c2, c1, denom):
    out = {k: (c2[k] - c1[k]) / denom for k in ("flops", "bytes", "wire")}
    out["collectives"] = {k: (c2["collectives"].get(k, 0.0)
                              - c1["collectives"].get(k, 0.0)) / denom
                          for k in set(c2["collectives"]) | set(c1["collectives"])}
    return out


def extrapolated_cost(cfg: ModelConfig, shape: ShapeConfig, mesh,
                      fsdp=None, donate_cache=False) -> Dict:
    """Exact-by-affinity cost extrapolation: per-layer costs measured from two
    reduced-depth UNROLLED lowers, scaled to the full depth. Needed because
    XLA cost_analysis counts a scanned (while-loop) body once regardless of
    trip count — a full unrolled compile of a 96-layer model is too slow, but
    cost is affine in the per-type layer counts, so two points suffice."""
    L = cfg.num_layers
    if cfg.family in ("dense", "vlm", "audio"):
        c2 = _cost_of(cfg.replace(num_layers=2), shape, mesh, fsdp, donate_cache)
        c4 = _cost_of(cfg.replace(num_layers=4), shape, mesh, fsdp, donate_cache)
        per = _diff(c4, c2, 2)
        base = _axpy(c2, per, -2)
        return _axpy(base, per, L)
    if cfg.family == "moe":
        kd = cfg.moe.first_k_dense
        cA = _cost_of(cfg.replace(num_layers=kd + 2), shape, mesh, fsdp, donate_cache)
        cB = _cost_of(cfg.replace(num_layers=kd + 4), shape, mesh, fsdp, donate_cache)
        per = _diff(cB, cA, 2)           # per MoE layer
        base = _axpy(cA, per, -2)        # includes the kd dense layers
        return _axpy(base, per, L - kd)
    if cfg.family == "hybrid":
        # all probe lowers stay <= 4 layers: deep unrolled hybrids make the
        # SPMD partitioner crawl on the 5-D SSD decay tensors.
        n_apps = L // cfg.shared_attn_every
        cM2 = _cost_of(cfg.replace(num_layers=2, shared_attn_every=0), shape, mesh, fsdp, donate_cache)
        cM4 = _cost_of(cfg.replace(num_layers=4, shared_attn_every=0), shape, mesh, fsdp, donate_cache)
        per_m = _diff(cM4, cM2, 2)       # per mamba layer
        base = _axpy(cM2, per_m, -2)
        cS1 = _cost_of(cfg.replace(num_layers=2, shared_attn_every=2), shape, mesh, fsdp, donate_cache)
        cS2 = _cost_of(cfg.replace(num_layers=4, shared_attn_every=2), shape, mesh, fsdp, donate_cache)
        # cS2-cS1 = 2 mamba layers + 1 shared app  =>  shared = diff - 2*per_m
        shared = _axpy(_diff(cS2, cS1, 1), per_m, -2)
        out = _axpy(base, per_m, L)
        return _axpy(out, shared, n_apps)
    if cfg.family == "ssm":
        import dataclasses as _dc
        g = cfg.xlstm.slstm_every
        n_groups = L // g
        pure_m = _dc.replace(cfg.xlstm, slstm_every=0)
        mixed = _dc.replace(cfg.xlstm, slstm_every=2)
        cM2 = _cost_of(cfg.replace(num_layers=2, xlstm=pure_m), shape, mesh, fsdp, donate_cache)
        cM4 = _cost_of(cfg.replace(num_layers=4, xlstm=pure_m), shape, mesh, fsdp, donate_cache)
        per_m = _diff(cM4, cM2, 2)       # per mLSTM block
        base = _axpy(cM2, per_m, -2)
        cS2 = _cost_of(cfg.replace(num_layers=2, xlstm=mixed), shape, mesh, fsdp, donate_cache)
        cS4 = _cost_of(cfg.replace(num_layers=4, xlstm=mixed), shape, mesh, fsdp, donate_cache)
        # cS4-cS2 = one (1 mLSTM + 1 sLSTM) group  =>  per_s = diff - per_m
        per_s = _axpy(_diff(cS4, cS2, 1), per_m, -1)
        out = _axpy(base, per_m, n_groups * (g - 1))
        return _axpy(out, per_s, n_groups)
    raise ValueError(cfg.family)


def run_cell(arch: str, shape_name: str, mesh, multi_pod: bool,
             verbose: bool = True, donate: bool = True,
             cfg_override=None, with_cost: bool = True, fsdp=None,
             donate_cache: bool = False) -> Dict:
    cfg = cfg_override or get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    # resolve FSDP on the FULL config: the reduced-depth cost probes must use
    # the same weight-sharding mode as the production compile
    if fsdp is None:
        fsdp = shape.kind == "train" and cfg.param_count() > 30e9
    t0 = time.time()
    # full-depth production compile (scan over layers): proof + memory
    compiled = _compile_cell(cfg, shape, mesh, donate, fsdp=fsdp,
                             donate_cache=donate_cache)
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    n_chips = mesh.devices.size
    if with_cost:
        cost = extrapolated_cost(cfg, shape, mesh, fsdp=fsdp,
                                 donate_cache=donate_cache)
    else:
        ca = compiled.cost_analysis() or {}
        cost = {"flops": float(ca.get("flops", 0.0)),
                "bytes": float(ca.get("bytes accessed", 0.0)),
                "wire": wire_bytes(collective_bytes(compiled.as_text())),
                "collectives": {}}
    flops_per_dev = cost["flops"]
    bytes_per_dev = cost["bytes"]
    wire = cost["wire"]
    cb = cost["collectives"]

    compute_term = flops_per_dev / PEAK_FLOPS
    memory_term = bytes_per_dev / HBM_BW
    # flash-adjusted: score tiles stay in VMEM on TPU (Pallas kernel)
    adj_bytes = max(bytes_per_dev - attn_score_bytes(cfg, shape) / n_chips,
                    0.05 * bytes_per_dev)
    memory_term_flash = adj_bytes / HBM_BW
    collective_term = wire / ICI_BW

    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        model_flops = 6.0 * n_active * shape.tokens
    elif shape.kind == "prefill":
        model_flops = 2.0 * n_active * shape.tokens
    else:
        model_flops = 2.0 * n_active * shape.global_batch
    hlo_flops_global = flops_per_dev * n_chips
    useful_ratio = model_flops / hlo_flops_global if hlo_flops_global else 0.0

    dominant = max((("compute", compute_term),
                    ("memory", memory_term_flash),
                    ("collective", collective_term)), key=lambda kv: kv[1])[0]
    res = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": int(n_chips),
        "compile_s": round(t_compile, 1),
        "flops_per_dev": flops_per_dev,
        "bytes_per_dev": bytes_per_dev,
        "wire_bytes_per_dev": wire,
        "collectives": {k: round(v, 1) for k, v in cb.items() if v},
        "compute_term_s": compute_term,
        "memory_term_s": memory_term,
        "memory_term_flash_s": memory_term_flash,
        "collective_term_s": collective_term,
        "dominant": dominant,
        "model_flops": model_flops,
        "useful_flops_ratio": useful_ratio,
        "params_b": n_params / 1e9,
        "active_params_b": n_active / 1e9,
        "arg_bytes_per_dev": int(ma.argument_size_in_bytes),
        "temp_bytes_per_dev": int(ma.temp_size_in_bytes),
        "out_bytes_per_dev": int(ma.output_size_in_bytes),
    }
    if verbose:
        print(f"[dryrun] {arch:22s} {shape_name:12s} mesh={res['mesh']:8s} "
              f"compile={t_compile:6.1f}s dom={dominant:10s} "
              f"C={compute_term*1e3:9.3f}ms M={memory_term*1e3:9.3f}ms "
              f"Mf={memory_term_flash*1e3:9.3f}ms "
              f"N={collective_term*1e3:9.3f}ms useful={useful_ratio:5.2f} "
              f"args/dev={ma.argument_size_in_bytes/1e9:6.2f}GB "
              f"temp/dev={ma.temp_size_in_bytes/1e9:6.2f}GB", flush=True)
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    results = []
    meshes = []
    if args.both_meshes:
        meshes = [(False, make_production_mesh(multi_pod=False)),
                  (True, make_production_mesh(multi_pod=True))]
    else:
        meshes = [(args.multi_pod, make_production_mesh(multi_pod=args.multi_pod))]

    arch_list = [a for a in ARCH_IDS if a != "llama3_70b"] if args.all \
        else args.arch.split(",")

    def _flush():
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)

    for arch in arch_list:
        cfg = get_config(arch)
        shapes = ([SHAPES_BY_NAME[args.shape]] if args.shape
                  else applicable_shapes(cfg))
        for sh in shapes:
            for mp, mesh in meshes:
                try:
                    # roofline cost terms are single-pod only (DESIGN.md);
                    # the multi-pod pass proves the "pod" axis shards.
                    results.append(run_cell(arch, sh.name, mesh, mp,
                                            with_cost=not mp))
                except Exception as e:  # a failing cell is a bug — surface it
                    print(f"[dryrun] FAIL {arch} {sh.name} "
                          f"{'2x16x16' if mp else '16x16'}: {type(e).__name__}: {e}",
                          flush=True)
                    results.append({"arch": arch, "shape": sh.name,
                                    "mesh": "2x16x16" if mp else "16x16",
                                    "error": f"{type(e).__name__}: {e}"})
                _flush()  # incremental: survive a killed sweep
    n_fail = sum(1 for r in results if "error" in r)
    print(f"[dryrun] {len(results) - n_fail}/{len(results)} cells OK")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
