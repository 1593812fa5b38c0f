import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks the device count on first init.
# Never set this in conftest/pyproject — smoke tests and benches see the
# single real CPU device; only the dry-run forges the production topology.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell the step function is jit'd with the production shardings
and lowered against ShapeDtypeStruct stand-ins (no allocation), then
compiled.  Success proves the distribution config is coherent: shardings
divide, collectives legal, memory bounded.  Outputs per cell:

  * compiled.memory_analysis()  — per-device bytes (fits 16 GiB HBM?)
  * compiled.cost_analysis()    — FLOPs / bytes for §Roofline
  * collective bytes parsed from the optimized HLO (launch/roofline.py)

CLI:
    python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
    python -m repro.launch.dryrun --arch catapultdb --shape search
    python -m repro.launch.dryrun --all [--multi-pod] [--out results/]
  --all iterates every assigned cell in a subprocess per cell (isolates
  failures, bounds compile-cache memory).
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ARCH_IDS, SHAPES, get_config
from repro.launch import roofline as rl
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.models import model as M
from repro.models.steps import (make_decode_step, make_prefill_step,
                                make_train_step)
from repro.optim import adamw

GiB = 2 ** 30
HBM_PER_CHIP = 16 * GiB    # TPU v5e


def opt_config(cfg) -> adamw.AdamWConfig:
    """arctic-480b: bf16 moments — f32 AdamW moments alone are 15 GiB/chip
    on a single pod (DESIGN.md §5 / EXPERIMENTS.md §Dry-run)."""
    if cfg.name == "arctic-480b":
        return adamw.AdamWConfig(moment_dtype="bfloat16")
    return adamw.AdamWConfig()


def _extend_fsdp(pspecs, mesh):
    """FSDP axes in param specs are written as the TUPLE ("data",); on the
    multi-pod mesh they widen to ("data", "pod") so arctic-scale expert
    weights shard over every data-parallel chip."""
    if "pod" not in mesh.axis_names:
        return pspecs

    def one(spec):
        if spec is None:
            return spec
        out = tuple(("data", "pod") if isinstance(e, tuple) and e == ("data",)
                    else e for e in spec)
        return jax.sharding.PartitionSpec(*out)

    return jax.tree_util.tree_map(one, pspecs,
                                  is_leaf=lambda x: isinstance(
                                      x, jax.sharding.PartitionSpec))


def input_specs(cfg, shape_name: str, mesh):
    """ShapeDtypeStruct stand-ins + shardings for one cell.

    Returns (fn, args_sds tuple, in_shardings tuple, donate_argnums,
    model_flops).
    """
    seq_len, global_batch, kind = SHAPES[shape_name]
    ba = batch_axes(mesh)
    model_size = mesh.shape["model"]
    ns = lambda spec: NamedSharding(mesh, spec)
    shard_tree = lambda pspecs: jax.tree_util.tree_map(ns, pspecs)

    param_sds = M.specs(cfg)
    param_pspecs = _extend_fsdp(M.pspecs(cfg), mesh)
    param_sh = shard_tree(param_pspecs)

    bspec = P(ba) if global_batch > 1 else P()
    tok = lambda b, s: jax.ShapeDtypeStruct((b, s), jnp.int32)

    def batch_specs(b, s):
        sds = {"tokens": tok(b, s)}
        sh = {"tokens": ns(bspec)}
        if cfg.family == "vlm":
            sds["tokens"] = tok(b, s - cfg.n_frontend_tokens)
            sds["patches"] = jax.ShapeDtypeStruct(
                (b, cfg.n_frontend_tokens, cfg.frontend_dim), jnp.float32)
            sh["patches"] = ns(bspec)
        if cfg.family == "encdec":
            sds["frames"] = jax.ShapeDtypeStruct(
                (b, s, cfg.frontend_dim), jnp.float32)
            sh["frames"] = ns(bspec)
        return sds, sh

    mf = rl.model_flops(cfg, kind, seq_len, global_batch)
    hbm = rl.analytic_hbm_bytes(cfg, kind, seq_len, global_batch)

    if kind == "train":
        ocfg = opt_config(cfg)
        fn = make_train_step(cfg, ocfg)
        mdt = jnp.dtype(ocfg.moment_dtype)
        mom_sds = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, mdt), param_sds)
        opt_pspecs = adamw.zero1_pspecs(param_sds, param_pspecs,
                                        data_size=mesh.shape["data"])
        opt_sds = adamw.AdamWState(mu=mom_sds, nu=mom_sds,
                                   step=jax.ShapeDtypeStruct((), jnp.int32))
        opt_sh = adamw.AdamWState(mu=shard_tree(opt_pspecs),
                                  nu=shard_tree(opt_pspecs), step=ns(P()))
        bsds, bsh = batch_specs(global_batch, seq_len)
        return (fn, (param_sds, opt_sds, bsds), (param_sh, opt_sh, bsh),
                (0, 1), mf, hbm)

    cache_sds = M.cache_specs(cfg, global_batch, seq_len, ba, model_size)
    cache_sh = shard_tree(M.cache_pspecs(cfg, global_batch, seq_len, ba,
                                         model_size))
    if kind == "prefill":
        fn = make_prefill_step(cfg)
        bsds, bsh = batch_specs(global_batch, seq_len)
        return (fn, (param_sds, bsds, cache_sds),
                (param_sh, bsh, cache_sh), (2,), mf, hbm)

    # decode: one new token against a seq_len cache
    fn = make_decode_step(cfg)
    tsds = tok(global_batch, 1)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return (fn, (param_sds, tsds, cache_sds, pos),
            (param_sh, ns(bspec), cache_sh, ns(P())), (2,), mf, hbm)


def catapultdb_specs(mesh):
    """The paper's own cell: one sharded catapulted search step."""
    from repro.configs.catapultdb import CONFIG as E
    from repro.core.beam_search import SearchSpec
    from repro.core.sharded import engine_state_specs, make_sharded_search

    sds, pspecs = engine_state_specs(mesh, E.n_vectors, E.dim, E.max_degree,
                                     E.lsh_bits, E.bucket_capacity)
    spec = SearchSpec(beam_width=E.beam_width, k=E.k, max_iters=E.max_iters)
    step = make_sharded_search(mesh, spec, E.n_vectors, E.lsh_bits)
    ns = lambda s: NamedSharding(mesh, s)
    qaxes = batch_axes(mesh)
    q_sds = jax.ShapeDtypeStruct((E.query_batch, E.dim), jnp.float32)
    state_sh = jax.tree_util.tree_map(ns, pspecs)
    # FLOPs of useful work: beam hops × degree × dim MACs per query
    mf = 2.0 * E.query_batch * E.max_iters * E.max_degree * E.dim
    # HBM: per hop gather R×(d vector + adjacency row) + beam state churn
    hbm = (E.query_batch * E.max_iters
           * (E.max_degree * (E.dim * 4 + 4) + E.beam_width * 16)
           + E.query_batch * E.bucket_capacity * 8)
    return (step, (sds, q_sds), (state_sh, ns(P(qaxes, None))), (0,), mf,
            hbm)


def run_cell(arch: str, shape: str, multi_pod: bool) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    if arch == "catapultdb":
        fn, args, shardings, donate, mf, hbm = catapultdb_specs(mesh)
    else:
        cfg = get_config(arch)
        if shape in cfg.skip_shapes:
            return {"arch": arch, "shape": shape,
                    "mesh": "multi_pod" if multi_pod else "single_pod",
                    "status": "skipped",
                    "reason": "inapplicable shape (DESIGN.md "
                              "§Arch-applicability)"}
        fn, args, shardings, donate, mf, hbm = input_specs(cfg, shape, mesh)

    with jax.set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
        lowered = jitted.lower(*args)
        hlo = lowered.as_text()
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        terms = rl.analyze(compiled, compiled.as_text(), mesh.size,
                           model_flops=mf, hbm_bytes=hbm)

    out = {
        "arch": arch, "shape": shape,
        "mesh": "multi_pod" if multi_pod else "single_pod",
        "chips": mesh.size,
        "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "roofline": terms.as_dict(),
    }
    arg = out["memory"]["argument_bytes"] or 0
    tmp = out["memory"]["temp_bytes"] or 0
    outb = out["memory"]["output_bytes"] or 0
    alias = out["memory"]["alias_bytes"] or 0
    peak = arg + tmp + outb - alias
    out["memory"]["peak_bytes_per_chip"] = peak
    out["memory"]["fits_16GiB"] = bool(peak <= HBM_PER_CHIP)
    return out


def all_cells():
    for arch in ARCH_IDS:
        for shape in SHAPES:
            yield arch, shape
    yield "catapultdb", "search"


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch")
    p.add_argument("--shape")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--all", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--out", default="benchmarks/dryrun_results")
    args = p.parse_args()

    if args.all:
        os.makedirs(args.out, exist_ok=True)
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        failures = []
        for arch, shape in all_cells():
            for mp in meshes:
                tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}"
                dest = os.path.join(args.out, tag + ".json")
                if os.path.exists(dest):
                    print(f"[dryrun] {tag}: cached")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out", dest]
                if mp:
                    cmd.append("--multi-pod")
                r = subprocess.run(cmd, capture_output=True, text=True)
                if r.returncode != 0:
                    failures.append(tag)
                    print(f"[dryrun] {tag}: FAILED\n{r.stdout[-2000:]}"
                          f"\n{r.stderr[-2000:]}")
                else:
                    print(r.stdout.strip().splitlines()[-1])
        print(f"[dryrun] done; {len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    res = run_cell(args.arch, args.shape, args.multi_pod)
    line = (f"[dryrun] {res['arch']}×{res['shape']}×{res['mesh']}: "
            f"{res['status']}")
    if res["status"] == "ok":
        peak = res["memory"]["peak_bytes_per_chip"]
        line += (f" peak={peak / GiB:.2f}GiB/chip "
                 f"fits={res['memory']['fits_16GiB']} "
                 f"dominant={res['roofline']['dominant']} "
                 f"compile={res['compile_s']}s")
    print(line)
    if args.out and args.out.endswith(".json"):
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    elif args.out:
        os.makedirs(args.out, exist_ok=True)
        tag = (f"{args.arch}__{args.shape}__"
               f"{'mp' if args.multi_pod else 'sp'}")
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
