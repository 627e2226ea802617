import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
"""Multi-pod dry-run: prove every (architecture × input shape × mesh) cell
lowers, SPMD-partitions and compiles, and extract the roofline terms.

For each cell:
  1. FULL compile — jit(step).lower(state, inputs).compile() with the real
     shardings; memory_analysis() proves per-device fit, cost_analysis() gives
     HLO flops/bytes, and the post-SPMD HLO text gives collective bytes.
  2. LAYER PROBE (LM cells) — XLA's cost analysis counts a while-loop body
     once, so scanned-layer models are costed as
         total = full + (n_layers − 1) × probe(single layer)
     where the probe compiles exactly one block (fwd for serving, fwd+bwd with
     the production remat policy for training) under the same mesh/sharding
     rules, with the flash-attention KV scan unrolled. Verified against the
     analytic 6·N·D model-FLOPs in §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-14b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
  python -m repro.launch.dryrun --all --out experiments/dryrun
Results are written incrementally as JSON, one file per cell.
"""
import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.launch.mesh import make_production_mesh
from repro.models.sharding import use_rules, named_sharding
from repro.train.loop import TrainState

# ---------------------------------------------------------------------------
# collective-bytes extraction from post-SPMD HLO
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_DONE_RE = re.compile(r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)-done")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device bytes moved by each collective kind (result sizes of every
    collective op in the partitioned module; -start/-done pairs counted once)."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if _DONE_RE.search(line):
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        out[m.group(2)] = out.get(m.group(2), 0) + _shape_bytes(m.group(1))
    return out


def cost_summary(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis() or {}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }


def memory_summary(compiled) -> Dict[str, float]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    keys = [
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    ]
    return {k: float(getattr(ma, k)) for k in keys if hasattr(ma, k)}


# ---------------------------------------------------------------------------
# cell compilation
# ---------------------------------------------------------------------------

def _attach_shardings(abstract, axes, mesh, rules):
    def attach(a, ax):
        if not hasattr(a, "shape"):
            return a
        ns = named_sharding(mesh, rules, *tuple(ax))
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=ns)

    return jax.tree.map(
        attach, abstract, axes,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


def compile_cell(
    arch: str, shape: str, multi_pod: bool, mesh=None
) -> Tuple[Any, Dict[str, Any]]:
    """Lower + compile the full step for one cell. Returns (compiled, record)."""
    spec = registry.get(arch)
    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    rules = registry.rules_for(spec, shape, multi_pod)
    t0 = time.perf_counter()
    with use_rules(rules, mesh):
        state, s_axes = registry.abstract_state(spec, shape)
        inputs, i_axes = registry.abstract_inputs(spec, shape)
        state = _attach_shardings(state, s_axes, mesh, rules)
        inputs = _attach_shardings(inputs, i_axes, mesh, rules)
        fn = registry.step_fn(spec, shape)
        kind = spec.shapes[shape]["kind"]
        # donate the train state: params/opt update in place (production setting;
        # without it the updated state doubles the resident param memory)
        donate = (0,) if kind == "train" else ()
        with mesh:
            lowered = jax.jit(fn, donate_argnums=donate).lower(state, inputs)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower
    hlo = compiled.as_text()
    rec = {
        "arch": arch,
        "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "kind": spec.shapes[shape]["kind"],
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "cost": cost_summary(compiled),
        "memory": memory_summary(compiled),
        "collectives_per_device_bytes": collective_bytes(hlo),
        "hlo_len": len(hlo),
    }
    return compiled, rec


def compile_lm_probe(
    arch: str, shape: str, multi_pod: bool, mesh=None
) -> Dict[str, Any]:
    """Single-layer cost probe for scanned LM cells (see module docstring)."""
    from repro.models import transformer as T

    spec = registry.get(arch)
    sh = spec.shapes[shape]
    kind = sh["kind"]
    cfg = dataclasses.replace(spec.cfg, flash_unroll=True)
    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    rules = registry.rules_for(spec, shape, multi_pod)
    b = sh["batch"]
    if kind == "train":  # probes see one microbatch; total scales by n_mb
        b = b // sh.get("n_microbatches", 1)
    s = sh["seq"]
    d = cfg.d_model

    params_abs, p_axes = registry.abstract_params(spec)
    layer_abs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), params_abs["layers"]
    )
    layer_axes = jax.tree.map(
        lambda ax: tuple(ax)[1:], p_axes["layers"],
        is_leaf=lambda x: isinstance(x, tuple),
    )

    with use_rules(rules, mesh):
        layer_in = _attach_shardings(layer_abs, layer_axes, mesh, rules)
        if kind == "train":
            x_abs = jax.ShapeDtypeStruct(
                (b, s, d), cfg.dtype, sharding=named_sharding(mesh, rules, "batch", "seq", "act_embed")
            )
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))

            block = T._remat_wrap(lambda x, lp: T._block(x, lp, cfg, positions)[0], cfg)

            def probe(x, lp):
                # fwd+bwd of one layer, incl. remat recompute — grads wrt x
                # (dgrad) and lp (wgrad)
                return jax.grad(lambda x, lp: block(x, lp).astype(jnp.float32).sum(), argnums=(0, 1))(x, lp)

        elif kind == "prefill":
            x_abs = jax.ShapeDtypeStruct(
                (b, s, d), cfg.dtype, sharding=named_sharding(mesh, rules, "batch", "seq", "act_embed")
            )
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))

            def probe(x, lp):
                return T.block_prefill(x, lp, cfg, positions, max_seq=s)

        else:  # decode
            cax = T.cache_logical_axes(b)
            cache_shape = (b, s, cfg.n_kv_heads, cfg.hd)
            kc_abs = jax.ShapeDtypeStruct(
                cache_shape, cfg.dtype, sharding=named_sharding(mesh, rules, *cax[1:])
            )
            x_abs = jax.ShapeDtypeStruct(
                (b, 1, d), cfg.dtype, sharding=named_sharding(mesh, rules, None if b == 1 else "batch", None, None)
            )
            positions = jnp.zeros((b, 1), jnp.int32)

            def probe(x, lp, kc, vc):
                return T.block_decode(x, lp, kc, vc, jnp.int32(0), positions, cfg, cax)

        with mesh:
            if kind == "decode":
                compiled = jax.jit(probe).lower(x_abs, layer_in, kc_abs, kc_abs).compile()
            else:
                compiled = jax.jit(probe).lower(x_abs, layer_in).compile()

    rec = {
        "cost": cost_summary(compiled),
        "collectives_per_device_bytes": collective_bytes(compiled.as_text()),
        "probe_batch": b,
    }

    # boundary probe: embed gather (+its scatter-grad), final norm, LM head,
    # loss — everything outside the layer stack, at microbatch size
    with use_rules(rules, mesh):
        params_b = {
            "embed": jax.ShapeDtypeStruct(
                (cfg.vocab, d), cfg.dtype, sharding=named_sharding(mesh, rules, "vocab", "fsdp")
            ),
            "lm_head": jax.ShapeDtypeStruct(
                (d, cfg.vocab), cfg.dtype, sharding=named_sharding(mesh, rules, "fsdp", "vocab")
            ),
            "final_norm": jax.ShapeDtypeStruct(
                (d,), cfg.dtype, sharding=named_sharding(mesh, rules, None)
            ),
        }
        if kind == "train":
            from repro.models import layers as Lx
            from repro.models.sharding import constrain as _con

            tok_abs = jax.ShapeDtypeStruct(
                (b, s), jnp.int32,
                sharding=named_sharding(mesh, rules, None if b == 1 else "batch", "seq"),
            )
            x_mid = jax.ShapeDtypeStruct(
                (b, s, d), cfg.dtype,
                sharding=named_sharding(mesh, rules, "batch", "seq", "act_embed"),
            )

            def boundary(pb, x_mid, tokens, labels):
                x0 = jnp.take(pb["embed"], tokens, axis=0)
                x = Lx.rmsnorm(x_mid + x0, pb["final_norm"])
                logits = jnp.einsum("bsd,dv->bsv", x, pb["lm_head"])
                logits = _con(logits, "batch", "seq", "act_vocab")
                return Lx.softmax_xent(logits, labels)

            bfn = jax.grad(boundary, argnums=(0, 1))
            with mesh:
                compiled_b = jax.jit(bfn).lower(params_b, x_mid, tok_abs, tok_abs).compile()
            rec["boundary"] = {
                "cost": cost_summary(compiled_b),
                "collectives_per_device_bytes": collective_bytes(compiled_b.as_text()),
            }
    return rec


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: Optional[str], mesh=None) -> Dict:
    spec = registry.get(arch)
    name = f"{arch}__{shape}__{'2x16x16' if multi_pod else '16x16'}"
    try:
        compiled, rec = compile_cell(arch, shape, multi_pod, mesh=mesh)
        print(f"[dryrun] {name}: compile ok "
              f"({rec['compile_s']}s, flops={rec['cost']['flops']:.3e})", flush=True)
        try:
            ma = compiled.memory_analysis()
            print(f"[dryrun]   memory_analysis: {rec['memory']}", flush=True)
        except Exception:
            pass
        del compiled
        if spec.family == "lm":
            probe = compile_lm_probe(arch, shape, multi_pod, mesh=mesh)
            rec["layer_probe"] = probe
            rec["n_layers"] = spec.cfg.n_layers
            print(f"[dryrun]   probe flops={probe['cost']['flops']:.3e}", flush=True)
        rec["status"] = "ok"
    except Exception as e:  # a failing cell is a bug — record it loudly
        rec = {
            "arch": arch, "shape": shape,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "status": "FAIL", "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
        print(f"[dryrun] {name}: FAIL {rec['error']}", flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, name + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def all_cells():
    for arch in registry.list_archs():
        spec = registry.get(arch)
        for shape in spec.shapes:
            yield arch, shape


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--families", default="lm,gnn,recsys,paper")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    meshes = []
    if not args.multi_pod_only:
        meshes.append(False)
    if not args.single_pod_only:
        meshes.append(True)

    fams = set(args.families.split(","))
    cells = (
        [(args.arch, args.shape)]
        if args.arch and args.shape
        else [
            (a, s) for a, s in all_cells()
            if registry.get(a).family in fams
        ]
    )
    results = []
    mesh_cache = {}
    for multi_pod in meshes:
        if multi_pod not in mesh_cache:
            mesh_cache[multi_pod] = make_production_mesh(multi_pod=multi_pod)
        for arch, shape in cells:
            name = f"{arch}__{shape}__{'2x16x16' if multi_pod else '16x16'}"
            path = os.path.join(args.out, name + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[dryrun] {name}: cached, skipping", flush=True)
                continue
            results.append(run_cell(arch, shape, multi_pod, args.out, mesh=mesh_cache[multi_pod]))
    fails = [r for r in results if r.get("status") != "ok"]
    print(f"[dryrun] done: {len(results) - len(fails)} ok, {len(fails)} failed", flush=True)
    if fails:
        for r in fails:
            print("  FAIL:", r["arch"], r["shape"], r["mesh"], r["error"], flush=True)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
