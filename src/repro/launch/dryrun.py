import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: prove the distribution config is coherent without TPUs.

For every (architecture x input shape) the appropriate step function is
lowered and compiled against the production mesh with ShapeDtypeStruct
stand-ins (no allocation):

  train_4k     -> train_step  (prioritized learner update)
  prefill_32k  -> score_step  (actor-side priority computation)
  decode_*     -> serve_step  (one token vs a seq_len cache)

Per combo it prints/records ``compiled.memory_analysis()`` (fits check),
``compiled.cost_analysis()`` (FLOPs/bytes for the roofline) and the
collective traffic parsed from the partitioned HLO; artifacts land in
``benchmarks/artifacts/`` for ``benchmarks/roofline.py``.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import INPUT_SHAPES, shape_applicable
from repro.launch import (hlo_analysis, sharding as shard_lib,
                          steps as steps_lib)
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16, data_axes,
                               make_production_mesh, num_chips)
from repro.models import registry, transformer
from repro.optim import optimizers as optim

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "benchmarks", "artifacts")


def probe_flops_scope(mesh) -> str:
    """Decide whether cost_analysis() reports global or per-device FLOPs by
    compiling a known matmul (2*M*K*N flops) sharded over the mesh."""
    M = K = N = 1024
    a = jax.ShapeDtypeStruct((M, K), jnp.float32)
    b = jax.ShapeDtypeStruct((K, N), jnp.float32)
    D = data_axes(mesh)
    sa = jax.NamedSharding(mesh, jax.sharding.PartitionSpec(D, None))
    sb = jax.NamedSharding(mesh, jax.sharding.PartitionSpec(None, "model"))
    compiled = jax.jit(lambda a, b: a @ b, in_shardings=(sa, sb)).lower(a, b).compile()
    flops = float(compiled.cost_analysis().get("flops", 0.0))
    expected_global = 2.0 * M * K * N
    return "global" if flops > expected_global / 2 else "per_device"


def active_param_count(cfg, param_shapes) -> tuple[int, int]:
    """(total, active) parameter counts; routed-expert tensors scale by
    top_k / num_experts."""
    total = active = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(param_shapes)[0]:
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        if cfg.moe is not None and len(leaf.shape) == 4:
            active += n * cfg.moe.top_k // cfg.moe.num_experts
        else:
            active += n
    return total, active


def model_flops(cfg, shape, active_params: int) -> float:
    """Analytic 'useful' FLOPs: 6*N*D train, 2*N*D prefill, 2*N*B decode."""
    if shape.kind == "train":
        return 6.0 * active_params * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * active_params * shape.seq_len * shape.global_batch
    return 2.0 * active_params * shape.global_batch  # one token per seq


def probe_layer_counts(cfg) -> tuple[int, int]:
    """(k, 2k) layer counts for the cost-extrapolation probes. k respects the
    arch's layer-group period (Zamba2: one shared-attn call per 6 layers)."""
    k = cfg.shared_attn_every or 2
    k = min(k, cfg.n_layers)
    return k, min(2 * k, cfg.n_layers)


def build_lowered(cfg, shape, mesh, probe_layers: int | None = None,
                  overrides: dict | None = None):
    """Lower the right step for (cfg, shape) against the mesh.

    Two flavors (DESIGN.md dry-run methodology):
    * full (probe_layers=None): the production path — scan-over-layers,
      chunked attention, per-layer remat for training, sharding constraints.
      This is the compile/fits proof; XLA's cost analysis counts while-loop
      bodies once, so its FLOPs/collectives are NOT used for the roofline.
    * probe (probe_layers=k): a k-layer UNROLLED variant with the attention
      KV loop unrolled too — exact instruction-level accounting. Costs are
      linearly extrapolated from the (k, 2k) probes: per-layer = (c2k-ck)/k,
      fixed (embed/head/loss) = ck - k*per-layer.
    """
    D = data_axes(mesh)
    if probe_layers is None:
        cfg = dataclasses.replace(
            cfg, attn_impl="chunked", scan_layers=True,
            remat=(shape.kind == "train"),
            act_sharding=(D, None, "model"))
    else:
        cfg = dataclasses.replace(
            cfg, n_layers=probe_layers,
            attn_impl="chunked", scan_layers=False, attn_unroll=True,
            remat=(shape.kind == "train"),
            act_sharding=(D, None, "model"))
    if overrides:
        ov = dict(overrides)
        if ov.get("act_sharding") == "data_only":
            ov["act_sharding"] = (D, None, None)
        elif ov.get("act_sharding") == "seq":
            ov["act_sharding"] = (D, "model", None)
        if "moe_groups" in ov:
            g = ov.pop("moe_groups")
            if cfg.moe is not None:
                ov["moe"] = dataclasses.replace(cfg.moe, dispatch_groups=g)
        elif ov.get("act_sharding") == "model":
            ov["act_sharding"] = (D, None, "model")
        cfg = dataclasses.replace(cfg, **ov)
    param_shapes = jax.eval_shape(lambda: transformer.init(cfg, jax.random.key(0)))
    p_shard = shard_lib.param_shardings(param_shapes, mesh)
    rep = shard_lib.replicated(mesh)

    if shape.kind == "train":
        optimizer = optim.adamw(3e-4)
        opt_shapes = jax.eval_shape(optimizer.init, param_shapes)
        o_shard = _opt_shardings(optimizer, param_shapes, p_shard, mesh)
        batch = registry.input_specs(cfg, shape)
        b_shard = shard_lib.batch_shardings(batch, mesh)
        step = steps_lib.make_train_step(cfg, optimizer)
        jitted = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard))
        return jitted.lower(param_shapes, opt_shapes, batch), cfg

    if shape.kind == "prefill":
        batch = registry.input_specs(cfg, shape)
        b_shard = shard_lib.batch_shardings(batch, mesh)
        step = steps_lib.make_score_step(cfg)
        jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
        return jitted.lower(param_shapes, batch), cfg

    # decode
    batch = registry.input_specs(cfg, shape)
    cache_shapes = registry.cache_specs(cfg, shape)
    c_shard = shard_lib.cache_shardings(cache_shapes, mesh)
    tok_shard = shard_lib.batch_shardings({"token": batch["token"]}, mesh)["token"]
    step = steps_lib.make_serve_step(cfg)
    jitted = jax.jit(step, in_shardings=(p_shard, c_shard, tok_shard, rep))
    return jitted.lower(param_shapes, cache_shapes, batch["token"],
                        batch["pos"]), cfg


def _opt_shardings(optimizer, param_shapes, p_shard, mesh):
    """Adam mu/nu shard exactly like their parameters; counters replicated."""
    rep = shard_lib.replicated(mesh)
    opt_shapes = jax.eval_shape(optimizer.init, param_shapes)
    # AdamState(step, mu, nu): mu/nu mirror params
    return type(opt_shapes)(step=rep,
                            mu=p_shard, nu=p_shard)


def run_combo(arch: str, shape_name: str, multi_pod: bool,
              flops_scope: str | None = None, verbose: bool = True,
              overrides: dict | None = None, tag: str = "") -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = num_chips(mesh)
    cfg = registry.get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "chips": chips, "status": "skipped", "reason": why,
           "variant": tag or "baseline"}
    if not ok:
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} @ {mesh_name}: SKIPPED ({why})")
        return rec

    # 1) full production compile — the "it lowers, compiles and fits" proof
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered, full_cfg = build_lowered(cfg, shape, mesh,
                                          overrides=overrides)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()

    # 2) (k, 2k)-layer unrolled probes — exact cost/collective accounting,
    #    linearly extrapolated to n_layers
    def probe_costs(layers: int) -> dict:
        with jax.set_mesh(mesh):
            plow, _ = build_lowered(cfg, shape, mesh, probe_layers=layers,
                                    overrides=overrides)
        pcomp = plow.compile()
        cost = pcomp.cost_analysis()
        coll = hlo_analysis.parse_collectives(pcomp.as_text())
        return {"flops": float(cost.get("flops", 0.0)),
                "bytes": float(cost.get("bytes accessed", 0.0)),
                "coll": float(coll.total_bytes),
                "coll_by_op": coll.bytes_by_op,
                "coll_counts": coll.count_by_op}

    k, k2 = probe_layer_counts(cfg)
    t0 = time.time()
    c1 = probe_costs(k)
    c2 = probe_costs(k2) if k2 > k else c1
    t_probe = time.time() - t0
    L = cfg.n_layers

    def extrap(a, b):
        if k2 == k:
            return b * (L / k)
        per_layer = (b - a) / (k2 - k)
        fixed = a - k * per_layer
        return fixed + L * per_layer

    flops = extrap(c1["flops"], c2["flops"])
    hbm_bytes = extrap(c1["bytes"], c2["bytes"])
    coll_bytes = extrap(c1["coll"], c2["coll"])
    coll_by_op = {op: extrap(c1["coll_by_op"].get(op, 0.0),
                             c2["coll_by_op"].get(op, 0.0))
                  for op in set(c1["coll_by_op"]) | set(c2["coll_by_op"])}

    if flops_scope is None:
        flops_scope = probe_flops_scope(mesh)
    terms = hlo_analysis.roofline_terms(
        flops, hbm_bytes, coll_bytes, chips, PEAK_FLOPS_BF16, HBM_BW,
        ICI_BW, flops_are_global=(flops_scope == "global"))

    param_shapes = jax.eval_shape(lambda: transformer.init(cfg, jax.random.key(0)))
    total_p, active_p = active_param_count(cfg, param_shapes)
    mf = model_flops(cfg, shape, active_p)
    global_flops = flops if flops_scope == "global" else flops * chips

    mem_fields = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(mem, f, None)
        if v is not None:
            mem_fields[f] = int(v)

    rec.update({
        "status": "ok",
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "probe_s": round(t_probe, 2), "probe_layers": [k, k2],
        "flops_scope": flops_scope,
        "hlo_flops": flops, "hlo_bytes": hbm_bytes,
        "hlo_flops_global": global_flops,
        "collective_bytes": coll_bytes,
        "collective_by_op": coll_by_op,
        "params_total": total_p, "params_active": active_p,
        "model_flops": mf,
        "useful_flops_ratio": mf / global_flops if global_flops else None,
        "memory_analysis": mem_fields,
        **terms,
    })
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} @ {mesh_name}: OK "
              f"(lower {t_lower:.1f}s, compile {t_compile:.1f}s, "
              f"probes {t_probe:.1f}s)")
        print(f"  memory_analysis: {mem_fields}")
        print(f"  cost_analysis (extrapolated from {k}/{k2}-layer probes): "
              f"flops={flops:.3e} bytes={hbm_bytes:.3e} [{flops_scope}]")
        print(f"  collectives: { {o: f'{b:.3e}' for o, b in coll_by_op.items()} }")
        print(f"  roofline: compute={terms['compute_s']:.4f}s "
              f"memory={terms['memory_s']:.4f}s "
              f"collective={terms['collective_s']:.4f}s "
              f"-> bottleneck: {terms['bottleneck']}")
        print(f"  MODEL_FLOPS={mf:.3e} useful-ratio="
              f"{rec['useful_flops_ratio']:.3f}" if rec["useful_flops_ratio"]
              else "")
    return rec


def artifact_path(arch, shape_name, multi_pod, tag: str = ""):
    mesh_name = "2x16x16" if multi_pod else "16x16"
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    safe = arch.replace("/", "_").replace(".", "_")
    suffix = f"__{tag}" if tag else ""
    return os.path.join(ARTIFACT_DIR,
                        f"dryrun_{safe}_{shape_name}_{mesh_name}{suffix}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    combos = ([(args.arch, args.shape)] if not args.all else
              [(a, s) for a in registry.ARCH_IDS for s in INPUT_SHAPES])
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    scope = probe_flops_scope(mesh)
    print(f"[dryrun] devices={num_chips(mesh)} flops_scope={scope}")
    failures = []
    for arch, shape_name in combos:
        path = artifact_path(arch, shape_name, args.multi_pod)
        if args.skip_existing and os.path.exists(path):
            print(f"[dryrun] {arch} x {shape_name}: cached")
            continue
        try:
            rec = run_combo(arch, shape_name, args.multi_pod, flops_scope=scope)
        except Exception as e:  # a failure here is a bug in the system
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape_name,
                   "mesh": "2x16x16" if args.multi_pod else "16x16",
                   "status": "error", "error": repr(e)}
            failures.append((arch, shape_name, repr(e)))
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
    if failures:
        print(f"[dryrun] FAILURES: {failures}")
        raise SystemExit(1)
    print("[dryrun] all combos OK")


if __name__ == "__main__":
    main()
