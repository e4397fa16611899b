import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Test/CI override (must still run before jax device init):
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

For each combination this driver builds the production mesh from abstract
ShapeDtypeStructs (no allocation), lowers the step function with the real
shardings, compiles it for the 512-way (or 256-way) host-device mesh, and
records:

  * ``compiled.memory_analysis()``  -- proves the program fits per device,
  * ``compiled.cost_analysis()``    -- HLO FLOPs / bytes for the roofline,
  * parsed collective bytes         -- the third roofline term,

into ``artifacts/dryrun/<arch>__<shape>__<mesh>[__tag].json``.

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  python -m repro.launch.dryrun --all               # 40 single-pod baselines
  python -m repro.launch.dryrun --all --multi-pod   # 512-chip pass
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax

from repro.configs import arch_names, get_config
from repro.launch import mesh as mesh_lib
from repro.launch.cache import enable_compile_cache
from repro.launch import shapes as shapes_lib
from repro.roofline import roofline_report
from repro.roofline.jaxpr_cost import jaxpr_cost

DEFAULT_OUT = "artifacts/dryrun"


def _mesh_tag(mesh) -> str:
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)


def _tokens_of(shape: shapes_lib.InputShape, T: int) -> int:
    if shape.kind == "train":
        return T * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return shape.global_batch * shape.seq_len
    return shape.global_batch          # decode: one token per sequence


def lower_combo(arch: str, shape_name: str, mesh, *, mixing: str = "ring",
                T: int = shapes_lib.DEFAULT_T, seq_shard: bool = False,
                loss_chunk: int = 0, donate: bool = False,
                moe_chunk: int = 0, attn_chunk: int = 0,
                moe_sharding: str = "", zero: bool = False,
                sp_mlp: bool = False, client_impl: str = "vmap"):
    """Build + lower + compile one combination; returns (compiled, meta).

    Optimization knobs (§Perf hillclimb; all off = paper-faithful baseline):
      seq_shard  -- Megatron-style sequence parallelism between blocks
      loss_chunk -- seq-chunked LM head + loss (no full fp32 logits)
      donate     -- donate the global params buffer to the train step
    """
    import dataclasses as _dc

    from repro.fl import distributed as dist
    from repro.models.sharding import set_activation_sharding

    shape = shapes_lib.SHAPES[shape_name]
    cfg = shapes_lib.production_config(get_config(arch), shape)
    if loss_chunk:
        cfg = _dc.replace(cfg, loss_chunk=loss_chunk)
    if moe_chunk:
        cfg = _dc.replace(cfg, moe_chunk=moe_chunk)
    if attn_chunk:
        cfg = _dc.replace(cfg, attn_chunk=attn_chunk)
    if moe_sharding:
        from repro.models.sharding import set_moe_sharding
        cfg = _dc.replace(cfg, moe_sharding=moe_sharding)
        set_moe_sharding(moe_sharding)
    set_activation_sharding("model" if seq_shard else None,
                            sp_mlp=sp_mlp)
    donate_kw = {}
    if donate and shape.kind == "train":
        donate_kw = dict(donate_argnums=(0,))
    elif donate and shape.kind == "decode":
        donate_kw = dict(donate_argnums=(1,))   # the KV/state cache

    if shape.kind == "train":
        inp = shapes_lib.train_inputs(cfg, shape, mesh, T=T, zero=zero)
        step = dist.make_train_step(cfg, mesh, mixing=mixing, jit=False,
                                    zero=zero, client_impl=client_impl)
        args = [inp["global_params"], inp["tokens"], inp["A"], inp["tau"],
                inp["m"], inp["eta"]]
        if cfg.frontend:
            args.append(inp["prefix"])
    elif shape.kind == "prefill":
        inp = shapes_lib.prefill_inputs(cfg, shape, mesh)
        step = dist.make_prefill_step(cfg, mesh, inp["_batch_axes"],
                                      cache_len=shapes_lib.cache_len_for(
                                          cfg, shape), jit=False)
        args = [inp["params"], inp["tokens"]]
        if cfg.frontend:
            args.append(inp["prefix"])
    else:
        inp = shapes_lib.decode_inputs(cfg, shape, mesh)
        step = dist.make_decode_step(cfg, mesh, inp["_batch_axes"],
                                     jit=False)
        args = [inp["params"], inp["cache"], inp["token"], inp["pos"]]

    with jax.set_mesh(mesh):
        lowered = jax.jit(step, **donate_kw).lower(*args)
        jcost = jaxpr_cost(jax.make_jaxpr(step)(*args))
    set_activation_sharding(None)
    if moe_sharding:
        from repro.models.sharding import set_moe_sharding
        set_moe_sharding("tensor")
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    return compiled, dict(cfg=cfg, shape=shape, compile_s=compile_s,
                          jcost=jcost)


def run_combo(arch: str, shape_name: str, *, multi_pod: bool = False,
              mesh_override=None, mixing: str = "ring",
              T: int = shapes_lib.DEFAULT_T, out_dir: str = DEFAULT_OUT,
              tag: str = "", seq_shard: bool = False, loss_chunk: int = 0,
              donate: bool = False, moe_chunk: int = 0,
              attn_chunk: int = 0, moe_sharding: str = "",
              zero: bool = False, sp_mlp: bool = False,
              client_impl: str = "vmap") -> Dict[str, Any]:
    mesh = (mesh_override if mesh_override is not None
            else mesh_lib.make_production_mesh(multi_pod=multi_pod))
    t0 = time.time()
    compiled, meta = lower_combo(arch, shape_name, mesh, mixing=mixing, T=T,
                                 seq_shard=seq_shard, loss_chunk=loss_chunk,
                                 donate=donate, moe_chunk=moe_chunk,
                                 attn_chunk=attn_chunk,
                                 moe_sharding=moe_sharding, zero=zero,
                                 sp_mlp=sp_mlp, client_impl=client_impl)
    total_s = time.time() - t0

    mem: Optional[Dict[str, float]] = None
    peak = None
    try:
        ma = compiled.memory_analysis()
        mem = {k: float(getattr(ma, k)) for k in
               ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes")
               if hasattr(ma, k)}
        if mem:
            peak = (mem.get("argument_size_in_bytes", 0.0)
                    + mem.get("temp_size_in_bytes", 0.0))
    except Exception:                                  # backend-dependent
        mem = None

    hlo = compiled.as_text()

    shape = shapes_lib.SHAPES[shape_name]
    chips = 1
    for a in mesh.axis_names:
        chips *= mesh.shape[a]
    jcost = meta["jcost"]
    report = roofline_report(
        arch=arch, shape=shape_name, mesh=_mesh_tag(mesh), chips=chips,
        flops_global=jcost["flops"], bytes_global=jcost["bytes"],
        hlo_text=hlo, cfg=meta["cfg"], kind=shape.kind,
        tokens=_tokens_of(shape, T), peak_memory=peak)

    record = report.as_dict()
    record.update(
        mixing=mixing if shape.kind == "train" else None,
        compile_s=meta["compile_s"], total_s=total_s,
        memory_analysis=mem,
        n_collective_ops=len(report.collective_per_device),
        hlo_bytes=len(hlo),
        opts=dict(seq_shard=seq_shard, loss_chunk=loss_chunk,
                  donate=donate, moe_chunk=moe_chunk,
                  attn_chunk=attn_chunk,
                  moe_sharding=moe_sharding or "tensor", zero=zero,
                  sp_mlp=sp_mlp, client_impl=client_impl),
    )
    os.makedirs(out_dir, exist_ok=True)
    name = f"{arch}__{shape_name}__{_mesh_tag(mesh)}"
    if tag:
        name += f"__{tag}"
    path = os.path.join(out_dir, name + ".json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1, default=str)
    record["_path"] = path
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=arch_names())
    ap.add_argument("--shape", choices=shapes_lib.shape_names())
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) combination")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mixing", default="ring",
                    choices=("ring", "gather", "einsum"))
    ap.add_argument("--T", type=int, default=shapes_lib.DEFAULT_T)
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--tag", default="")
    ap.add_argument("--mesh", default="",
                    help="debug mesh, e.g. '2,2,2' (pod,data,model)")
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence parallelism between blocks (§Perf)")
    ap.add_argument("--loss-chunk", type=int, default=0,
                    help="seq-chunked LM head+loss (§Perf)")
    ap.add_argument("--donate", action="store_true",
                    help="donate global params buffer (§Perf)")
    ap.add_argument("--moe-chunk", type=int, default=0,
                    help="token-chunked MoE dispatch (§Perf)")
    ap.add_argument("--attn-chunk", type=int, default=0,
                    help="override attention query-chunk (§Perf)")
    ap.add_argument("--moe-sharding", default="",
                    choices=("", "tensor", "expert"),
                    help="MoE weight layout (§Perf)")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO-style global param sharding (§Perf)")
    ap.add_argument("--sp-mlp", action="store_true",
                    help="explicit shard_map SP-MLP (§Perf; needs --seq-shard)")
    ap.add_argument("--client-impl", default="vmap",
                    choices=("vmap", "shardmap"))
    args = ap.parse_args(argv)
    enable_compile_cache()

    mesh_override = None
    if args.mesh:
        shape_t = tuple(int(x) for x in args.mesh.split(","))
        mesh_override = mesh_lib.make_debug_mesh(shape_t)

    combos = ([(a, s) for a in arch_names()
               for s in shapes_lib.shape_names()]
              if args.all else [(args.arch, args.shape)])
    if not args.all and (args.arch is None or args.shape is None):
        ap.error("--arch and --shape required (or --all)")

    failures = 0
    for arch, shape_name in combos:
        try:
            rec = run_combo(arch, shape_name, multi_pod=args.multi_pod,
                            mesh_override=mesh_override, mixing=args.mixing,
                            T=args.T, out_dir=args.out, tag=args.tag,
                            seq_shard=args.seq_shard,
                            loss_chunk=args.loss_chunk, donate=args.donate,
                            moe_chunk=args.moe_chunk,
                            attn_chunk=args.attn_chunk,
                            moe_sharding=args.moe_sharding,
                            zero=args.zero, sp_mlp=args.sp_mlp,
                            client_impl=args.client_impl)
            coll = sum(rec["collective_per_device"].values())
            print(f"OK   {arch:22s} {shape_name:12s} {rec['mesh']:9s} "
                  f"flops/dev={rec['flops_per_device']:.3e} "
                  f"coll/dev={coll:.3e}B "
                  f"dom={rec['dominant']:10s} "
                  f"compile={rec['compile_s']:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"FAIL {arch:22s} {shape_name:12s}", flush=True)
            traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
