import os
# compile-only tool: 512 fake CPU devices, pinned to the CPU even on a
# machine whose default backend is a TPU
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (architecture × shape × mesh)
# cell with ShapeDtypeStruct inputs (zero allocation), record
# memory_analysis / cost_analysis / per-collective bytes for §Roofline.
#
# MUST be invoked as its own process (the XLA_FLAGS line above runs before
# any other import, including jax) — never import this module from a
# process that already initialized jax with 1 device.
#
# Usage:
#     PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-32b \
#         --shape train_4k [--multi-pod] [--out artifacts/dryrun]
#     PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
#     PYTHONPATH=src python -m repro.launch.dryrun --gp karoo-kat7-pod

import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import all_arch_names, get_config
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.launch import sharding as SH
from repro.models import model as Md
from repro.models.transformer import ShardingPolicy
from repro.optim.adamw import for_config

# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1}

_COLL_RE = re.compile(
    r"=\s+(?P<types>[^=]*?)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<start>-start)?\(", re.X)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _type_bytes(types: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(types):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by collectives, summed per op kind.

    Shapes in post-partitioning HLO are per-device. We count the RESULT
    shape of each collective (for all-gather that is the gathered size ≈
    wire bytes × n/(n-1); for reduce-scatter the input is n× larger than
    the wire volume — we count the result, a lower bound; all-reduce wire
    cost is ~2× its size on a ring — recorded raw here, modeled in
    benchmarks/roofline.py)."""
    out: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        op = m.group("op")
        out[op] = out.get(op, 0) + _type_bytes(m.group("types"))
    return out


# ---------------------------------------------------------------------------
# cell lowering
# ---------------------------------------------------------------------------


def make_policy(mesh) -> ShardingPolicy:
    dp = 1
    for a in batch_axes(mesh):
        dp *= mesh.shape[a]
    return ShardingPolicy(batch=batch_axes(mesh), model="model",
                          tp_size=mesh.shape["model"], dp_size=dp)


def lower_cell(cfg, shape_name: str, mesh):
    """Returns the `jax.stages.Lowered` for one (arch × shape × mesh) cell."""
    cfg = cfg.with_policy(make_policy(mesh))
    kind, specs = Md.input_specs(cfg, shape_name)

    if kind == "train":
        opt = for_config(cfg)

        def init_state(key):
            params = Md.init_params(cfg, key)
            return {"params": params, "opt": opt.init(params),
                    "step": jnp.zeros((), jnp.int32)}

        state_shapes = jax.eval_shape(init_state, jax.random.PRNGKey(0))
        state_specs = SH.train_state_specs(cfg, state_shapes, mesh)
        state_sds = SH.named(mesh, state_specs, state_shapes)
        batch_sds = SH.named(mesh, SH.batch_specs(cfg, specs), specs)
        step = Md.make_train_step(cfg, opt, param_specs=state_specs["params"])
        with jax.set_mesh(mesh):
            metric_shapes = jax.eval_shape(step, state_shapes, specs)[1]
            out_shardings = (
                jax.tree.map(lambda s: SH.NamedSharding(mesh, s), state_specs),
                jax.tree.map(lambda _: SH.NamedSharding(mesh, SH.P()), metric_shapes))
            return jax.jit(step, donate_argnums=(0,),
                           out_shardings=out_shardings).lower(state_sds, batch_sds)

    params_shapes = jax.eval_shape(lambda k: Md.init_params(cfg, k), jax.random.PRNGKey(0))
    params_sds = SH.named(mesh, SH.param_specs(cfg, params_shapes, mesh), params_shapes)
    b_axes = tuple(cfg.policy.batch)
    logits_spec = (SH.P(b_axes, None, "model")
                   if cfg.vocab % (mesh.shape["model"]) == 0 else SH.P(b_axes, None, None))

    if kind == "prefill":
        batch_sds = SH.named(mesh, SH.batch_specs(cfg, specs), specs)
        S = Md.SHAPES[shape_name]["seq"]
        cache_shapes = jax.eval_shape(lambda: Md.init_cache(cfg, Md.SHAPES[shape_name]["batch"], S))
        cache_out = SH.cache_specs(cfg, cache_shapes, mesh, seq_shard=False)

        def prefill_fn(p, b):
            return Md.prefill(cfg, p, b, max_len=S)

        with jax.set_mesh(mesh):
            out_shardings = (SH.NamedSharding(mesh, logits_spec),
                             jax.tree.map(lambda s: SH.NamedSharding(mesh, s), cache_out))
            return jax.jit(prefill_fn, out_shardings=out_shardings).lower(
                params_sds, batch_sds)

    # decode
    seq_shard = Md.SHAPES[shape_name]["batch"] == 1  # long-context: CP over seq
    cache_out_specs = SH.cache_specs(cfg, specs["cache"], mesh, seq_shard=seq_shard)
    cache_sds = SH.named(mesh, cache_out_specs, specs["cache"])
    tok_sds = SH.named(mesh, jax.tree.map(lambda _: SH.P(b_axes, None)
                                          if not seq_shard else SH.P(None, None),
                                          specs["token"]), specs["token"])
    len_sds = specs["cur_len"]
    step = Md.make_serve_step(cfg)
    with jax.set_mesh(mesh):
        # pinning cache out_shardings == in_shardings lets donation alias the
        # cache buffers (decode must be in-place at 100+ GB caches)
        long_logits = (SH.P(None, None, "model")
                       if cfg.vocab % mesh.shape["model"] == 0 else SH.P(None, None, None))
        out_shardings = (
            SH.NamedSharding(mesh, logits_spec if not seq_shard else long_logits),
            jax.tree.map(lambda s: SH.NamedSharding(mesh, s), cache_out_specs))
        return jax.jit(step, donate_argnums=(1,), out_shardings=out_shardings).lower(
            params_sds, cache_sds, tok_sds, len_sds)


def analyze(lowered, *, want_hlo: bool = False) -> dict:
    t0 = time.time()
    compiled = lowered.compile()
    dt = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    rec = {
        "compile_s": round(dt, 1),
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "collective_bytes": coll,
        "memory": {
            "argument_gb": mem.argument_size_in_bytes / 2**30,
            "output_gb": mem.output_size_in_bytes / 2**30,
            "temp_gb": mem.temp_size_in_bytes / 2**30,
            "alias_gb": mem.alias_size_in_bytes / 2**30,
            "code_mb": mem.generated_code_size_in_bytes / 2**20,
        },
    }
    if want_hlo:
        rec["hlo"] = hlo
    return rec


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             keep_hlo: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    if not Md.shape_supported(cfg, shape_name):
        rec = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
               "status": "skip:full-attn"}
    else:
        try:
            lowered = lower_cell(cfg, shape_name, mesh)
            rec = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                   "status": "ok", **analyze(lowered, want_hlo=keep_hlo)}
        except Exception as e:  # a failure here is a bug in the system
            rec = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                   "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-4000:]}
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = "mp" if multi_pod else "sp"
        hlo = rec.pop("hlo", None)
        path = os.path.join(out_dir, f"{arch}_{shape_name}_{tag}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        if hlo is not None:
            with open(path.replace(".json", ".hlo.txt"), "w") as f:
                f.write(hlo)
    return rec


# ---------------------------------------------------------------------------
# GP (paper-workload) cells
# ---------------------------------------------------------------------------

GP_CELLS = {
    # name: (pop, n_features, rows, kernel)  — production-scale Karoo runs
    "karoo-kat7-pod": (4096, 9, 4_194_304, "c"),
    "karoo-ligo-pod": (1024, 1373, 524_288, "c"),
    "karoo-kepler-pod": (8192, 2, 1_048_576, "r"),
}


def run_gp_cell(name: str, multi_pod: bool, out_dir: str, keep_hlo: bool = False,
                eval_impl: str = "jnp", block_steps: int = 10) -> dict:
    """Lower one production GP cell as a K-generation evolution block —
    the scan-inside-shard_map program `GPSession.evolve()` dispatches, so
    the cost/memory record covers the real device-resident loop surface
    (collectives included), not a single step."""
    from repro.core import GPState
    from repro.core.engine import cache_width
    from repro.gp import GPSession

    pop, F, rows, kern = GP_CELLS[name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    sess = GPSession(name=name, pop_size=pop, max_depth=5, n_features=F,
                     n_consts=8, kernel=kern, backend=eval_impl, topology=mesh)
    cfg = sess.config
    spec = cfg.tree_spec
    block, specs = sess.build_sharded_block(block_steps)
    N = spec.num_nodes
    E = cache_width(cfg)
    sds = jax.ShapeDtypeStruct
    state_shapes = GPState(
        key=sds((2,), jnp.uint32), op=sds((pop, N), jnp.int32),
        arg=sds((pop, N), jnp.int32), fitness=sds((pop,), jnp.float32),
        best_op=sds((N,), jnp.int32), best_arg=sds((N,), jnp.int32),
        best_fitness=sds((), jnp.float32), generation=sds((), jnp.int32),
        cache_op=sds((E, N), jnp.int32), cache_arg=sds((E, N), jnp.int32),
        cache_fit=sds((E,), jnp.float32))
    state_sds = SH.named(mesh, specs["state"], state_shapes)
    X_sds = SH.named(mesh, specs["X"], sds((F, rows), jnp.float32))
    y_sds = SH.named(mesh, specs["y"], sds((rows,), jnp.float32))
    w_sds = SH.named(mesh, specs["weight"], sds((rows,), jnp.float32))
    limit_sds = SH.named(mesh, specs["limit"], sds((), jnp.int32))
    try:
        with jax.set_mesh(mesh):
            lowered = jax.jit(block, donate_argnums=(0,)).lower(
                state_sds, X_sds, y_sds, w_sds, limit_sds)
        rec = {"arch": name, "shape": f"pop{pop}_rows{rows}_F{F}_K{block_steps}",
               "multi_pod": multi_pod, "status": "ok",
               **analyze(lowered, want_hlo=keep_hlo)}
    except Exception as e:
        rec = {"arch": name, "multi_pod": multi_pod, "status": "FAIL",
               "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-4000:]}
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = "mp" if multi_pod else "sp"
        hlo = rec.pop("hlo", None)
        path = os.path.join(out_dir, f"{name}_{tag}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        if hlo is not None:
            with open(path.replace(".json", ".hlo.txt"), "w") as f:
                f.write(hlo)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--gp")
    ap.add_argument("--gp-impl", default="jnp")
    ap.add_argument("--gp-block", type=int, default=10,
                    help="generations per lowered GP evolution block")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    args = ap.parse_args()

    if args.gp:
        rec = run_gp_cell(args.gp, args.multi_pod, args.out, args.keep_hlo,
                          args.gp_impl, block_steps=args.gp_block)
        print(json.dumps({k: v for k, v in rec.items() if k != "trace"}, indent=1))
        raise SystemExit(0 if rec["status"] != "FAIL" else 1)

    cells = ([(args.arch, args.shape)] if args.arch and args.shape else
             [(a, s) for a in all_arch_names() for s in Md.SHAPES])
    if not args.all and not (args.arch and args.shape):
        ap.error("need --arch+--shape, --gp, or --all")
    failures = 0
    for arch, shape in cells:
        rec = run_cell(arch, shape, args.multi_pod, args.out, args.keep_hlo)
        line = {k: rec.get(k) for k in ("arch", "shape", "status", "compile_s",
                                        "flops", "error")}
        print(json.dumps(line))
        failures += rec["status"] == "FAIL"
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
