"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes, print memory/cost analysis, extract roofline terms.

MUST be the very first lines — jax locks the device count on first init:
"""
import os  # noqa: E402
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from dataclasses import dataclass  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import SHAPES, get_config, cells  # noqa: E402
from repro.launch.mesh import dp_axes, make_production_mesh  # noqa: E402
from repro.models.layers import Policy  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.optim.adamw import AdamWConfig, adamw_init, opt_state_specs  # noqa: E402
from repro.parallel.sharding import (MeshRules, param_specs,  # noqa: E402
                                     sanitize_specs)
from repro.runtime.train import RunConfig, make_train_step  # noqa: E402

# ---------------------------------------------------------------- hardware
CHIP_PEAK_FLOPS = 197e12     # TPU v5e bf16
CHIP_HBM_BW = 819e9          # B/s
LINK_BW = 50e9               # B/s per ICI link (conservative single link)

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


# ---------------------------------------------------------------- policies
@dataclass
class DryrunPolicy:
    param_dtype: str
    opt_dtype: str
    microbatches: int
    remat: str
    attn_impl: str = "chunked"
    fsdp: bool = False               # shard params over data axes too
    sequence_parallel: bool = True   # SP for the residual stream (train)
    sp_prefill: bool = False         # context-parallel prefill (perf knob)
    q_chunk: int = 1024
    kv_chunk: int = 512
    grad_accum_dtype: str = "float32"
    fold_depth: int = 4

    def policy(self) -> Policy:
        return Policy(jnp.dtype(self.param_dtype), jnp.bfloat16)


BIG = {"llama3-405b", "arctic-480b", "dbrx-132b", "qwen2-72b"}
MID = {"llama-3.2-vision-11b", "musicgen-large", "zamba2-2.7b",
       "llama-20b-paper"}


def dryrun_policy(arch: str, overrides: dict | None = None) -> DryrunPolicy:
    if arch in BIG:
        p = DryrunPolicy("bfloat16", "int8", 16, "full", fsdp=True)
    elif arch in MID:
        p = DryrunPolicy("float32", "bfloat16", 4, "full", fsdp=True)
    else:
        p = DryrunPolicy("float32", "float32", 4, "none")
    for k, v in (overrides or {}).items():
        setattr(p, k, v)
    return p


# ---------------------------------------------------------------- specs
def _sds(shapes_tree, specs_tree, mesh):
    specs_tree = sanitize_specs(specs_tree, shapes_tree, mesh)

    def mk(s, p):
        return jax.ShapeDtypeStruct(s.shape, s.dtype,
                                    sharding=NamedSharding(mesh, p))
    return jax.tree.map(mk, shapes_tree, specs_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _param_specs_tree(model, mesh):
    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = param_specs(pshapes)
    return pshapes, pspecs


def cache_specs(cfg, mesh, batch: int, max_seq: int, policy: Policy,
                model) -> tuple:
    """(cache_shapes, cache_specs) for serve_step lowering."""
    dp = dp_axes(mesh)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    batch_ok = batch % n_dp == 0 and batch >= n_dp
    bspec = dp if batch_ok else None
    sspec = None if batch_ok else dp  # batch=1 long-context: shard the seq
    shapes = jax.eval_shape(lambda: model.init_cache(batch, max_seq))
    fam = cfg.family

    def spec_for(path_key: str, ndim: int) -> P:
        if fam in ("dense", "moe", "audio"):
            # k/v [L,B,T,KV,hd]
            return P(None, bspec, sspec, "model", None)
        if fam == "vlm":
            if path_key.startswith("cross"):
                return P(None, bspec, None, "model", None)
            return P(None, None, bspec, sspec, "model", None)
        if fam == "ssm":
            if path_key == "state":
                return P(None, bspec, "model", None, None)
            return P(None, bspec, None, "model")
        if fam == "hybrid":
            if path_key == "state":
                return P(None, None, bspec, "model", None, None)
            if path_key == "conv":
                return P(None, None, bspec, None, "model")
            return P(None, bspec, sspec, "model", None)
        raise ValueError(fam)

    specs = {k: spec_for(k, v.ndim) for k, v in shapes.items()}
    return shapes, specs


# ---------------------------------------------------------------- builders
def build_cell(arch: str, shape_name: str, mesh, overrides=None):
    """Returns (fn, arg_specs, info) ready for jax.jit(fn).lower(*specs)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    pol = dryrun_policy(arch, overrides)
    sp = (bool(pol.sequence_parallel) and shape.kind == "train") or \
        (bool(pol.sp_prefill) and shape.kind == "prefill")
    rules = MeshRules(mesh, sequence_parallel=sp)
    model = build_model(cfg, policy=pol.policy(), constrain=rules, mesh=mesh,
                        attn_impl=pol.attn_impl, remat=pol.remat,
                        fold_depth=pol.fold_depth)
    if hasattr(model, "q_chunk"):
        model.q_chunk = pol.q_chunk
        model.kv_chunk = pol.kv_chunk
    dp = dp_axes(mesh)
    pshapes, pspecs = _param_specs_tree(model, mesh)
    if pol.fsdp:
        from repro.parallel.sharding import zero_spec
        pspecs = jax.tree.map(
            lambda s, sh: zero_spec(s, sh.shape, mesh, axes=dp),
            pspecs, pshapes, is_leaf=lambda x: isinstance(x, P))
    params_sds = _sds(pshapes, pspecs, mesh)
    B, S = shape.global_batch, shape.seq_len
    info = {"arch": arch, "shape": shape_name, "kind": shape.kind,
            "family": cfg.family, "tokens": shape.tokens,
            "param_count": cfg.param_count(),
            "active_param_count": cfg.active_param_count(),
            "policy": vars(pol).copy()}

    def tok_sds(b, s):
        return jax.ShapeDtypeStruct(
            (b, s), jnp.int32, sharding=NamedSharding(
                mesh, P(dp if b % _n(mesh, dp) == 0 else None, None)))

    vis_sds = None
    if cfg.family == "vlm":
        vis_sds = jax.ShapeDtypeStruct(
            (B, cfg.vision_tokens, cfg.vision_d), jnp.bfloat16,
            sharding=NamedSharding(mesh, P(dp, None, None)))

    if shape.kind == "train":
        run = RunConfig(model=cfg, global_batch=B, seq_len=S,
                        num_microbatches=pol.microbatches,
                        opt=AdamWConfig(state_dtype=pol.opt_dtype),
                        param_dtype=pol.param_dtype, remat=pol.remat,
                        attn_impl=pol.attn_impl,
                        grad_accum_dtype=pol.grad_accum_dtype)
        step_fn = make_train_step(model, run, mesh=mesh)
        oshapes = jax.eval_shape(
            lambda p: adamw_init(p, run.opt), pshapes)
        ospecs = opt_state_specs(pspecs, pshapes, mesh, run.opt)
        opt_sds = _sds(oshapes, ospecs, mesh)
        batch = {"tokens": tok_sds(B, S), "labels": tok_sds(B, S)}
        if vis_sds is not None:
            batch["vision_embeds"] = vis_sds
        step_sds = jax.ShapeDtypeStruct((), jnp.int32)
        return step_fn, (params_sds, opt_sds, batch, step_sds), info

    if shape.kind == "prefill":
        def prefill_fn(params, tokens, vision_embeds=None):
            cache = model.init_cache(B, S)
            kw = ({"vision_embeds": vision_embeds}
                  if vision_embeds is not None else {})
            return model.prefill(params, tokens, cache, **kw)
        args = (params_sds, tok_sds(B, S))
        if vis_sds is not None:
            args = args + (vis_sds,)
        return prefill_fn, args, info

    # decode: one new token against a full cache
    cshapes, cspecs = cache_specs(cfg, mesh, B, S, pol.policy(), model)
    cache_sds = _sds(cshapes, cspecs, mesh)
    tok = jax.ShapeDtypeStruct(
        (B, 1), jnp.int32, sharding=NamedSharding(
            mesh, P(dp if B % _n(mesh, dp) == 0 else None, None)))
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def decode_fn(params, token, cache, pos):
        return model.decode_step(params, token, cache, pos)

    return decode_fn, (params_sds, tok, cache_sds, pos), info


def _n(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return max(n, 1)


# ---------------------------------------------------------------- analysis
def parse_collective_bytes(hlo: str) -> dict:
    """Per-device collective payloads from the (SPMD-partitioned) HLO."""
    out = {k: {"count": 0, "result_bytes": 0, "wire_bytes": 0}
           for k in COLLECTIVES}
    type_re = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    for line in hlo.splitlines():
        m = re.search(r"=\s*(.+?)\s+(" + "|".join(COLLECTIVES) +
                      r")(?:-start|-done)?\(", line)
        if not m:
            continue
        restype, op = m.group(1), m.group(2)
        if "-done(" in line:
            continue  # avoid double count of async pairs
        nbytes = 0
        for dt, dims in type_re.findall(restype):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        gsize = _group_size(line)
        wire = _wire_bytes(op, nbytes, gsize)
        out[op]["count"] += 1
        out[op]["result_bytes"] += nbytes
        out[op]["wire_bytes"] += wire
    out["total_wire_bytes"] = sum(
        v["wire_bytes"] for k, v in out.items() if isinstance(v, dict))
    out["total_result_bytes"] = sum(
        v["result_bytes"] for k, v in out.items() if isinstance(v, dict))
    return out


def _group_size(line: str) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([0-9, ]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    return 2


def _wire_bytes(op: str, result_bytes: int, n: int) -> int:
    """Ring-schedule wire traffic per device, from the RESULT size."""
    if n <= 1:
        return 0
    if op == "all-reduce":
        return int(2 * result_bytes * (n - 1) / n)
    if op == "all-gather":
        return int(result_bytes * (n - 1) / n)
    if op == "reduce-scatter":
        return int(result_bytes * (n - 1))  # result is the 1/n shard
    if op == "all-to-all":
        return int(result_bytes * (n - 1) / n)
    return result_bytes  # collective-permute


def analyze(compiled, lowered, info, chips: int) -> dict:
    from repro.launch.hlo_analysis import analyze_hlo

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    scan_aware = analyze_hlo(hlo)  # multiplies through while-loop trip counts
    flops = float(scan_aware["flops"])            # per-device
    bytes_acc = float(scan_aware["traffic_bytes"])
    wire = float(scan_aware["total_wire_bytes"])
    # train = fwd+bwd (6·N·D); prefill/decode = forward only (2·N·D)
    flops_per_param = 6.0 if info["kind"] == "train" else 2.0
    model_flops = flops_per_param * info["active_param_count"] * info["tokens"]
    t_compute = flops / CHIP_PEAK_FLOPS
    t_memory = bytes_acc / CHIP_HBM_BW
    t_coll = wire / LINK_BW
    dominant = max(("compute", t_compute), ("memory", t_memory),
                   ("collective", t_coll), key=lambda kv: kv[1])[0]
    return {
        **info,
        "chips": chips,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": (mem.argument_size_in_bytes
                           + mem.output_size_in_bytes
                           + mem.temp_size_in_bytes),
        },
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "cost_analysis_flops_once": float(ca.get("flops", 0.0)),
        "cost_analysis_bytes_once": float(ca.get("bytes accessed", 0.0)),
        "collectives": scan_aware["collectives"],
        "total_wire_bytes": wire,
        "model_flops_global": model_flops,
        "model_flops_per_device": model_flops / chips,
        "useful_flops_ratio": (model_flops / chips) / flops if flops else 0.0,
        "roofline_s": {"compute": t_compute, "memory": t_memory,
                       "collective": t_coll},
        "dominant": dominant,
    }


# ---------------------------------------------------------------- driver
def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str | None = None, overrides=None,
             tag: str = "") -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 512 if multi_pod else 256
    t0 = time.time()
    fn, specs, info = build_cell(arch, shape_name, mesh, overrides)
    with mesh:
        lowered = jax.jit(fn).lower(*specs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    res = analyze(compiled, lowered, info, chips)
    res["mesh"] = "2x16x16" if multi_pod else "16x16"
    res["lower_s"] = round(t_lower, 1)
    res["compile_s"] = round(t_compile, 1)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        fname = (f"{arch}_{shape_name}_{res['mesh'].replace('x', '-')}"
                 f"{suffix}.json")
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(res, f, indent=1)
    return res


def main():
    ap = argparse.ArgumentParser(description="FLARE repro multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every assigned cell on this mesh (in-process)")
    ap.add_argument("--out", default="dryrun_out")
    ap.add_argument("--override", default="",
                    help="k=v,k=v policy overrides (e.g. attn_impl=folded)")
    ap.add_argument("--tag", default="", help="suffix for output json")
    args = ap.parse_args()

    overrides = {}
    for kv in args.override.split(","):
        if "=" in kv:
            k, v = kv.split("=", 1)
            overrides[k] = int(v) if v.isdigit() else v

    todo = []
    if args.all:
        todo = [(a, s) for a, s, _ in cells()]
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        todo = [(args.arch, args.shape)]

    failures = []
    for arch, shape_name in todo:
        try:
            r = run_cell(arch, shape_name, args.multi_pod, args.out,
                         overrides, args.tag)
            mem_gb = r["memory"]["peak_bytes"] / 2 ** 30
            roof = r["roofline_s"]
            print(f"OK   {arch:22s} {shape_name:12s} {r['mesh']:8s} "
                  f"peak/dev={mem_gb:6.2f}GiB "
                  f"compute={roof['compute'] * 1e3:8.2f}ms "
                  f"memory={roof['memory'] * 1e3:8.2f}ms "
                  f"coll={roof['collective'] * 1e3:8.2f}ms "
                  f"dom={r['dominant']:10s} "
                  f"useful={r['useful_flops_ratio']:.2f} "
                  f"[compile {r['compile_s']}s]",
                  flush=True)
        except Exception as e:  # noqa: BLE001
            failures.append((arch, shape_name, repr(e)[:300]))
            print(f"FAIL {arch:22s} {shape_name:12s}: {e!r}"[:240],
                  flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
