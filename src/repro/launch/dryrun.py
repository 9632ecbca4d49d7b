"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For every (architecture x input shape) cell and both production meshes
(single pod 16x16, multi-pod 2x16x16) this lowers + compiles the step
function against ShapeDtypeStruct inputs, records ``memory_analysis()`` /
``cost_analysis()``, and parses the post-SPMD optimized HLO for collective
operand bytes (all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute) — the inputs to EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun
"""
import argparse
import json
import os
import re
import time
import traceback

import jax
from jax.sharding import NamedSharding

from repro.configs import SHAPES, all_cells, get_arch
from repro.dist.sharding import (
    batch_pspecs,
    cache_pspecs,
    param_pspecs,
    to_named,
    use_mesh,
)
from repro.launch.mesh import make_production_mesh, mesh_tag
from repro.models.registry import build_model, input_specs
from repro.train.step import (
    TrainConfig,
    init_train_state,
    make_optimizer,
    make_train_step,
    train_state_pspecs,
)


# ----------------------------------------------------------------------
# HLO collective parsing
# ----------------------------------------------------------------------

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum per-device operand bytes of every collective op in optimized HLO.

    Returns {op_kind: {'bytes': int, 'count': int}} plus a '_total'."""
    out = {k: {"bytes": 0, "count": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # ops look like: %name = bf16[128,32]{1,0} all-gather(...), replica_groups=...
        m = re.match(r"^%?[\w.-]+\s*=\s*(\([^)]*\)|[^=]*?)\s*([a-z0-9-]+)\(", s)
        if not m:
            continue
        op = m.group(2)
        kind = None
        for k in _COLLECTIVES:
            if op == k or op.startswith(k + "-"):  # e.g. all-reduce-start
                kind = k
                break
        if kind is None or op.endswith("-done"):
            continue
        out[kind]["bytes"] += _shape_bytes(m.group(1))
        out[kind]["count"] += 1
    out["_total_bytes"] = sum(v["bytes"] for k, v in out.items() if isinstance(v, dict))
    out["_total_count"] = sum(v["count"] for k, v in out.items() if isinstance(v, dict))
    return out


# ----------------------------------------------------------------------
# expert-parallel all-to-all ledger (counted from the model itself)
# ----------------------------------------------------------------------

def count_ep_alltoall_bytes(cfg, B: int, qlen: int, *, train: bool = False) -> dict:
    """Count the EP dispatch/combine all-to-all payload of one MoE layer
    straight from the executed model implementation.

    ``repro.models.moe.dispatch_geometry`` is the same code path
    ``moe_layer`` uses to build the dispatched-activation tensor
    ``(G, E, C, d)`` — the tensor the expert mesh axis re-shards — so this
    is the dry-run's ground-truth byte ledger for EP traffic, in the
    layer's compute dtype. ``core.decomposer.ep_alltoall_bytes`` must
    reproduce ``dispatch_bytes``/``combine_bytes`` *exactly* from its
    workload dict (pinned per MoE arch by ``tests/test_parallelism.py``
    and gated in ``benchmarks/bench_parallelism.py``); the decomposer's
    ``CommCall``s and this ledger therefore price the same tensor the
    optimized-HLO collective pass above streams.

    Returns per-hop and per-layer byte counts plus the geometry:
    ``{"dispatch_bytes", "combine_bytes", "layer_bytes", "model_bytes",
    "G", "group", "capacity"}`` (``model_bytes`` = per-layer x n_layers —
    the whole step's EP traffic)."""
    from repro.core.decomposer import COMPUTE_DTYPE_BYTES
    from repro.models.moe import dispatch_geometry

    if not cfg.n_experts:
        raise ValueError(f"{cfg.name} is not an MoE architecture")
    T = B * qlen
    G, Sg, C = dispatch_geometry(cfg, T, train=train)
    b = COMPUTE_DTYPE_BYTES[cfg.compute_dtype]
    hop = float(G * cfg.n_experts * C * cfg.d_model * b)
    return {
        "dispatch_bytes": hop,
        "combine_bytes": hop,
        "layer_bytes": 2.0 * hop,
        "model_bytes": 2.0 * hop * cfg.n_layers,
        "G": G,
        "group": Sg,
        "capacity": C,
    }


# ----------------------------------------------------------------------
# per-cell lowering
# ----------------------------------------------------------------------


def state_pspecs(state_shapes, mesh):
    return train_state_pspecs(state_shapes, mesh)


def lower_cell(arch: str, shape_name: str, multi_pod: bool, pipeline: bool = False):
    """Lower + compile one cell. Returns (lowered, compiled, meta).

    ``pipeline=True`` lowers against the pipeline-parallel production
    mesh (4-way ``pipe`` axis, see ``launch.mesh``); parameter/batch
    sharding rules replicate over the ``pipe`` axis (only the ``"pipe"``
    role claims it), so the lowering stays coherent while the mesh leaves
    room for ``dist.pipeline.pipeline_forward`` stage placement."""
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    if not cfg.supports_shape(shape):
        raise ValueError(f"{arch} x {shape_name}: documented skip (DESIGN.md)")
    mesh = make_production_mesh(multi_pod=multi_pod, pipeline=pipeline)
    api = build_model(cfg)
    specs = input_specs(cfg, shape)

    with use_mesh(mesh):
        params_shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
        p_sh = to_named(param_pspecs(params_shapes, mesh), mesh)

        if shape.kind == "train":
            tc = TrainConfig()
            optimizer = make_optimizer(tc)
            state_shapes = jax.eval_shape(
                lambda: init_train_state(
                    api, optimizer, jax.random.PRNGKey(0),
                    compress_grads=tc.compress_grads,
                )
            )
            s_spec = state_pspecs(state_shapes, mesh)
            s_sh = to_named(s_spec, mesh)
            b_sh = to_named(batch_pspecs(specs["batch"], mesh), mesh)
            step_fn = make_train_step(api, optimizer, tc)
            lowered = jax.jit(
                step_fn,
                in_shardings=(s_sh, b_sh),
                out_shardings=(s_sh, None),
                donate_argnums=(0,),
            ).lower(state_shapes, specs["batch"])
        elif shape.kind == "prefill":
            from repro.dist.sharding import resolve_pspec

            b_sh = to_named(batch_pspecs(specs["batch"], mesh), mesh)
            cache_shapes = jax.eval_shape(
                lambda p, b: api.prefill(p, b)[1], params_shapes, specs["batch"]
            )
            c_out = to_named(cache_pspecs(cache_shapes, mesh), mesh)
            logits_sh = NamedSharding(
                mesh,
                resolve_pspec((shape.global_batch, cfg.padded_vocab), ("batch", "tp"), mesh),
            )
            lowered = jax.jit(
                api.prefill,
                in_shardings=(p_sh, b_sh),
                out_shardings=(logits_sh, c_out),
            ).lower(params_shapes, specs["batch"])
        else:  # decode
            from repro.dist.sharding import resolve_pspec

            c_sh = to_named(cache_pspecs(specs["cache"], mesh), mesh)
            tok_sh = to_named(batch_pspecs({"t": specs["tokens"]}, mesh), mesh)["t"]
            logits_sh = NamedSharding(
                mesh,
                resolve_pspec((shape.global_batch, cfg.padded_vocab), ("batch", "tp"), mesh),
            )
            lowered = jax.jit(
                api.decode,
                in_shardings=(p_sh, c_sh, tok_sh, tok_sh),
                out_shardings=(logits_sh, c_sh),
                donate_argnums=(1,),
            ).lower(params_shapes, specs["cache"], specs["tokens"], specs["positions"])

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    meta = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_tag(multi_pod=multi_pod, pipeline=pipeline),
        "n_devices": mesh.devices.size,
        "compile_s": round(compile_s, 1),
    }
    return lowered, compiled, meta


def analyze(lowered, compiled, meta) -> dict:
    from repro.roofline.hlo_cost import analyze_hlo

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # noqa: BLE001
        mem_d = {"error": str(e)}
    text = compiled.as_text()
    walk = analyze_hlo(text)  # loop-aware per-device costs (see roofline/)
    out = dict(meta)
    # raw XLA numbers (while bodies counted once — kept for reference)
    out["xla_flops_raw"] = cost.get("flops")
    out["xla_bytes_raw"] = cost.get("bytes accessed")
    # loop-aware per-device numbers used by §Roofline
    out["flops"] = walk.flops
    out["dot_flops"] = walk.dot_flops
    out["vector_ops"] = walk.vector_ops
    out["transcendentals"] = walk.transcendentals
    out["hbm_bytes"] = walk.hbm_bytes
    out["memory"] = mem_d
    out["collectives"] = {
        **walk.collectives,
        "_total_bytes": walk.collective_bytes,
    }
    out["unknown_ops"] = walk.unknown_ops
    out["hlo_lines"] = len(text.splitlines())
    cfg = get_arch(meta["arch"])
    if cfg.n_experts:
        # the analytical EP all-to-all ledger next to the HLO-counted
        # collectives: per-layer dispatch/combine bytes of the dispatched
        # (G, E, C, d) tensor, from the model's own grouping/capacity code
        shape = SHAPES[meta["shape"]]
        qlen = 1 if shape.kind == "decode" else shape.seq_len
        out["ep_alltoall"] = count_ep_alltoall_bytes(
            cfg, shape.global_batch, qlen, train=shape.kind == "train"
        )
    return out


def run_cell(
    arch: str, shape_name: str, multi_pod: bool, print_analysis=True, hlo_path=None,
    pipeline: bool = False,
) -> dict:
    lowered, compiled, meta = lower_cell(arch, shape_name, multi_pod, pipeline)
    result = analyze(lowered, compiled, meta)
    if hlo_path:
        import zstandard

        with open(hlo_path, "wb") as f:
            f.write(zstandard.compress(compiled.as_text().encode()))
    if print_analysis:
        print(json.dumps(result, indent=2, default=str))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--pipeline", action="store_true",
                    help="lower against the pipeline-parallel production "
                         "mesh (4-way pipe axis; see launch.mesh)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    # the production meshes need 512 virtual host devices; the flag must be
    # in the environment before the backend initializes
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
    )

    os.makedirs(args.out, exist_ok=True)
    cells = all_cells() if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if (args.all or args.both_meshes) else [args.multi_pod]

    n_fail = 0
    for arch, shape_name in cells:
        for mp in meshes:
            tag = f"{arch}__{shape_name}__{mesh_tag(multi_pod=mp, pipeline=args.pipeline)}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip cached] {tag}")
                continue
            print(f"[dry-run] {tag}", flush=True)
            try:
                hlo_dir = os.path.join(args.out, "hlo")
                os.makedirs(hlo_dir, exist_ok=True)
                result = run_cell(
                    arch, shape_name, mp, print_analysis=False,
                    hlo_path=os.path.join(hlo_dir, tag + ".hlo.zst"),
                    pipeline=args.pipeline,
                )
                with open(path, "w") as f:
                    json.dump(result, f, indent=2, default=str)
                print(
                    f"  ok: flops={result['flops']:.3e} "
                    f"coll={result['collectives']['_total_bytes']:.3e}B "
                    f"compile={result['compile_s']}s",
                    flush=True,
                )
            except Exception:  # noqa: BLE001
                n_fail += 1
                with open(path + ".fail", "w") as f:
                    f.write(traceback.format_exc())
                print(f"  FAIL ({tag}) — see {path}.fail", flush=True)
    print(f"done; {n_fail} failures")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
