import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax import anywhere in the process
(jax locks the device count at first init), which is why this module must
only be run as a script / fresh process:

    PYTHONPATH=src python -m repro.launch.dryrun --arch olmoe-1b-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun

Per cell it produces a JSON artifact with:
  * compile success on the (16,16) single-pod AND (2,16,16) multi-pod mesh
  * compiled.memory_analysis() — bytes per device (proves it fits)
  * compiled.cost_analysis()  — raw XLA numbers (scan bodies counted once!)
  * scan-aware HLO analysis    — corrected flops / bytes / collective bytes
    (launch/hlo_analysis.py) and the three §Roofline terms.

Post-SPMD HLO is the per-device program, so analyzer outputs are per-chip;
MODEL_FLOPS is divided by the chip count for the usefulness ratio.
"""

import argparse
import dataclasses
import json
import traceback
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (
    ARCH_IDS, SHAPES, get_arch, is_cell_supported, skip_reason,
)
from repro.obs import tracing
from repro.configs.base import ArchConfig
from repro.configs.shapes import ShapeConfig
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh, n_chips
from repro.models import common, registry, transformer
from repro.serve import engine
from repro.sharding import ctx as shardctx
from repro.sharding import specs as shardspecs
from repro.train.step import TrainConfig, init_train_state, train_step


# ----------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no device allocation)
# ----------------------------------------------------------------------------


def input_specs(arch: ArchConfig, shape: ShapeConfig):
    """Aval dict for the cell's step function."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind in ("train", "prefill"):
        batch = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        if shape.kind == "train":
            batch["targets"] = jax.ShapeDtypeStruct((b, s), i32)
        if arch.mrope:
            batch["positions"] = jax.ShapeDtypeStruct((3, b, s), i32)
        if arch.frontend_stub_len:
            batch["frontend_embeds"] = jax.ShapeDtypeStruct(
                (b, arch.frontend_stub_len, arch.d_model), common.ACT_DTYPE
            )
        return batch
    # decode: one new token against a kv_len cache
    cache = jax.eval_shape(lambda: engine.init_cache(arch, b, s))
    return {
        "token": jax.ShapeDtypeStruct((b,), i32),
        "pos": jax.ShapeDtypeStruct((), i32),
        "cache": cache,
    }


def _state_shardings(state_avals, arch, mesh):
    param_specs = shardspecs.param_specs(
        state_avals["params"], arch,
        data_size=mesh.shape.get("data", 1),
        model_size=mesh.shape.get("model", 1),
    )

    def named(tree):
        return jax.tree.map(lambda sp: NamedSharding(mesh, sp), tree)

    return {
        "params": named(param_specs),
        "opt": {
            "mu": named(param_specs),
            "nu": named(param_specs),
            "count": NamedSharding(mesh, P()),
            "ef": None,
        },
        "step": NamedSharding(mesh, P()),
        "sketch": NamedSharding(mesh, P()),
    }


def _batch_shardings(batch_avals, arch, mesh, global_batch):
    return {
        k: NamedSharding(mesh, shardspecs.batch_spec(arch, mesh, global_batch, k))
        for k in batch_avals
    }


# ----------------------------------------------------------------------------
# per-cell lowering
# ----------------------------------------------------------------------------


def pick_grad_accum(arch: ArchConfig, shape: ShapeConfig, n_dp: int) -> int:
    """Smallest power-of-two microbatching that bounds layer-boundary
    residuals to ~3 GB/device (the activation term of the 16 GB budget)."""
    if shape.kind != "train":
        return 1
    b_loc = max(1, shape.global_batch // n_dp)
    resid = arch.n_layers * b_loc * shape.seq_len * arch.d_model * 2  # bf16
    mu = 1
    while (
        resid / mu > 3e9
        and mu * 2 <= b_loc
        and shape.global_batch % (mu * 2) == 0
        and (shape.global_batch // (mu * 2)) % n_dp == 0
    ):
        mu *= 2
    return mu


def lower_cell(arch_id: str, shape_name: str, multi_pod: bool = False,
               overrides: Optional[dict] = None, tp: int = 16,
               grad_accum: int = 0):
    """Lower + compile one cell. Returns (compiled, meta)."""
    arch = get_arch(arch_id)
    if overrides:
        arch = dataclasses.replace(arch, **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod, tp=tp)
    chips = n_chips(mesh)

    dp = shardspecs.data_axes(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    cfg = TrainConfig(
        grad_accum=grad_accum or pick_grad_accum(arch, shape, n_dp)
    )
    hints = shardctx.ActivationHints(
        batch_axes=dp if shape.global_batch % n_dp == 0 else (),
        model_axis="model",
        seq_parallel=bool(int(os.environ.get("REPRO_SEQ_PARALLEL", "0"))),
    )

    with mesh, shardctx.use_hints(hints):
        if shape.kind == "train":
            state_avals = jax.eval_shape(
                lambda k: init_train_state(k, arch, cfg),
                jax.ShapeDtypeStruct((2,), jnp.uint32),
            )
            batch_avals = input_specs(arch, shape)
            state_sh = _state_shardings(state_avals, arch, mesh)
            batch_sh = _batch_shardings(batch_avals, arch, mesh, shape.global_batch)
            fn = partial(train_step, arch=arch, cfg=cfg)
            lowered = jax.jit(
                fn,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            ).lower(state_avals, batch_avals)
        elif shape.kind == "prefill":
            params_avals = jax.eval_shape(
                lambda k: transformer.init_params(k, arch),
                jax.ShapeDtypeStruct((2,), jnp.uint32),
            )
            batch_avals = input_specs(arch, shape)
            params_sh = jax.tree.map(
                lambda sp: NamedSharding(mesh, sp),
                shardspecs.param_specs(
                    params_avals, arch,
                    data_size=mesh.shape.get("data", 1),
                    model_size=mesh.shape.get("model", 1),
                ),
            )
            batch_sh = _batch_shardings(batch_avals, arch, mesh, shape.global_batch)

            def prefill_fn(params, batch):
                logits, _, states = transformer.forward(
                    params, batch, arch, collect_state=True
                )
                return logits[:, -1, :], states

            lowered = jax.jit(
                prefill_fn, in_shardings=(params_sh, batch_sh)
            ).lower(params_avals, batch_avals)
        else:  # decode
            params_avals = jax.eval_shape(
                lambda k: transformer.init_params(k, arch),
                jax.ShapeDtypeStruct((2,), jnp.uint32),
            )
            ins = input_specs(arch, shape)
            params_sh = jax.tree.map(
                lambda sp: NamedSharding(mesh, sp),
                shardspecs.param_specs(
                    params_avals, arch,
                    data_size=mesh.shape.get("data", 1),
                    model_size=mesh.shape.get("model", 1),
                ),
            )
            cache_sh = jax.tree.map(
                lambda sp: NamedSharding(mesh, sp),
                shardspecs.cache_specs(ins["cache"], arch, mesh, shape.global_batch),
            )
            tok_sh = NamedSharding(
                mesh, shardspecs.batch_spec(arch, mesh, shape.global_batch, "token")
            )
            fn = partial(engine.decode_step, arch=arch)
            lowered = jax.jit(
                fn,
                in_shardings=(params_sh, cache_sh, tok_sh, NamedSharding(mesh, P())),
                out_shardings=(None, cache_sh),
                donate_argnums=(1,),
            ).lower(params_avals, ins["cache"], ins["token"], ins["pos"])

    compiled = lowered.compile()
    return compiled, {"chips": chips, "kind": shape.kind}


def _memory_dict(compiled) -> dict:
    out = {}
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {"unavailable": True}
    if ma is None:
        return {"unavailable": True}
    for field in (
        "temp_size_in_bytes", "argument_size_in_bytes",
        "output_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ma, field, None)
        if v is not None:
            out[field] = int(v)
    if "temp_size_in_bytes" in out and "argument_size_in_bytes" in out:
        out["peak_bytes_per_device_est"] = (
            out["temp_size_in_bytes"]
            + out["argument_size_in_bytes"]
            + out.get("output_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0)
        )
    return out


def run_cell(
    arch_id: str, shape_name: str, multi_pod: bool, out_dir: Optional[str],
    overrides: Optional[dict] = None, tag: str = "", tp: int = 16,
    grad_accum: int = 0,
) -> dict:
    arch = get_arch(arch_id)
    shape = SHAPES[shape_name]
    mesh_tag = ("pod2x16x16" if multi_pod else "pod16x16") + tag
    record = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_tag,
        "kind": shape.kind, "status": "ok", "overrides": overrides or {},
    }
    if not is_cell_supported(arch, shape):
        record["status"] = "skipped"
        record["skip_reason"] = skip_reason(arch, shape)
        _write(record, out_dir)
        return record

    try:
        watch = tracing.Stopwatch()
        watch.start()
        with tracing.span("dryrun.compile", cell=f"{arch_id}/{shape_name}"):
            compiled, meta = lower_cell(arch_id, shape_name, multi_pod,
                                        overrides, tp, grad_accum)
        chips = meta["chips"]
        record["compile_s"] = round(watch.stop(), 1)
        record["memory_analysis"] = _memory_dict(compiled)
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, list):  # older jax returns [per-device dict]
                ca = ca[0]
            record["cost_analysis_raw"] = {
                "flops": float(ca.get("flops", -1)),
                "bytes_accessed": float(ca.get("bytes accessed", -1)),
            }
        except Exception:
            record["cost_analysis_raw"] = {"unavailable": True}

        analysis = hlo_analysis.analyze(compiled.as_text())
        model_flops = registry.model_flops_per_token(arch, shape.kind) * (
            shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        )
        terms = hlo_analysis.roofline_terms(
            analysis, n_chips=1, model_flops=model_flops / chips
        )
        record["roofline"] = {
            k: (v if not isinstance(v, float) else float(v))
            for k, v in terms.items()
        }
        record["hlo"] = {
            "n_while_loops": analysis.n_while_loops,
            "trip_counts": analysis.trip_counts,
        }
        record["model_flops_global"] = model_flops
        record["chips"] = chips
    except Exception as e:  # a failing cell is a bug — record it loudly
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    _write(record, out_dir)
    return record


def _write(record: dict, out_dir: Optional[str]):
    if not out_dir:
        return
    os.makedirs(out_dir, exist_ok=True)
    name = f"{record['arch']}__{record['shape']}__{record['mesh']}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(record, f, indent=2, default=str)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="arch field override key=value (int/float/str)")
    ap.add_argument("--tag", default="", help="suffix for the artifact name")
    ap.add_argument("--tp", type=int, default=16,
                    help="TP degree (256//tp becomes DP) — §Perf variant")
    ap.add_argument("--grad-accum", type=int, default=0,
                    help="override microbatch count (0 = auto)")
    args = ap.parse_args()

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            try:
                v = float(v)
            except ValueError:
                pass
        overrides[k] = v

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a, s in cells:
        for mp in meshes:
            tag = ("pod2x16x16" if mp else "pod16x16") + args.tag
            path = os.path.join(args.out, f"{a}__{s}__{tag}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"[dryrun] skip existing {a} {s} {tag}")
                continue
            rec = run_cell(a, s, mp, args.out, overrides or None, args.tag,
                           args.tp, args.grad_accum)
            status = rec["status"]
            extra = ""
            if status == "ok":
                r = rec["roofline"]
                extra = (
                    f" dominant={r['dominant']} bound={r['bound_s']:.4f}s "
                    f"useful={r.get('useful_flop_ratio', 0):.3f}"
                )
            elif status == "error":
                extra = " " + rec["error"][:160]
            print(f"[dryrun] {a:18s} {s:12s} {tag:10s} {status}{extra}", flush=True)


if __name__ == "__main__":
    main()
