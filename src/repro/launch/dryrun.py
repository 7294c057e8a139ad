import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the
# device count at first init).  512 host devices back the 16x16 single-pod
# and 2x16x16 multi-pod production meshes with zero real allocation --
# everything below lowers/compiles against ShapeDtypeStructs only.
os.environ.setdefault("REPRO_STRICT_BF16_DOTS", "1")  # TPU-faithful dots

"""Multi-pod dry-run driver (deliverable e) + roofline metering (g).

Per (arch x shape x mesh) cell:

  1. **Production compile** -- the scanned-over-layers program with full
     in/out shardings; ``.lower().compile()`` success proves the sharding
     config is coherent; ``memory_analysis()`` proves it fits per device.
  2. **Metered compiles** (single-pod only) -- XLA's cost analysis counts
     a ``while`` body ONCE regardless of trip count (verified empirically:
     8-layer scan reports 1/8 the unrolled FLOPs), so roofline terms from
     the production artifact would undercount by the layer count.  We
     therefore lower three shallow variants whose loops all have trip
     count 1 (1 period / 2 periods / +tail, with single-block attention
     and fully-unrolled SSD chunk scans), and recover

         F_body  = F(2P) - F(1P)        per-period cost
         F_fixed = 2 F(1P) - F(2P)      embed/head/loss cost
         F_tail  = F(1P+tail) - F(1P)
         F_total = F_fixed + n_periods * F_body + F_tail

     for FLOPs, bytes and per-kind collective bytes alike.  Single-block
     attention computes identical matmul FLOPs to the chunked schedule
     (same S^2 pairs), so the substitution is exact for the dot terms.

Usage:
  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all          # every cell, both meshes
"""
import argparse
import dataclasses
import json
import re
import subprocess
import sys
import time
import traceback

__all__ = ["run_cell", "collective_bytes", "main"]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# wire multipliers: all-reduce ~ reduce-scatter + all-gather on a ring
_WIRE_MULT = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
              "all-to-all": 1.0, "collective-permute": 1.0}


def _shape_bytes(segment: str) -> int:
    best = 0
    for dt, dims in _SHAPE_RE.findall(segment):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        best = max(best, n * _DTYPE_BYTES[dt])
    return best


def collective_bytes(hlo_text: str) -> dict:
    """Per-kind payload bytes (per device) of every collective op.

    Payload = largest shape on the op's LHS (handles async start tuples);
    ``wire`` applies ring multipliers (all-reduce = 2x).
    """
    out = {k: 0 for k in _WIRE_MULT}
    count = {k: 0 for k in _WIRE_MULT}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        lhs = line.split(m.group(0))[0]
        b = _shape_bytes(lhs)
        out[op] += b
        count[op] += 1
    wire = sum(out[k] * _WIRE_MULT[k] for k in out)
    return {"payload_bytes": out, "op_counts": count, "wire_bytes": wire}


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` across jax versions.

    Older jax returns a list with one properties-dict per computation;
    newer jax returns the dict directly.
    """
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca


def _mem_dict(ma) -> dict:
    keys = ["generated_code_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes",
            "temp_size_in_bytes"]
    return {k: int(getattr(ma, k)) for k in keys}


# ----------------------------------------------------------------------
# per-cell lowering
# ----------------------------------------------------------------------


def _build(cfg):
    if cfg.enc_dec:
        from repro.models.whisper import WhisperED
        return WhisperED(cfg)
    from repro.models.transformer import StackedLM
    return StackedLM(cfg)


def _f16_standin(cfg):
    """Swap bf16 -> f16 for the compile-only dry-run.

    XLA:CPU's float-normalization-bf16 legalizes every bf16 op by
    converting operands to f32 -- including whole (L,B,S,D) stacked scan
    residuals and caches, inflating memory_analysis ~2-4x vs the TPU
    target (measured: 35.6 -> 10.4 GB on llama3.2 train_4k).  f16 is a
    2-byte dtype the CPU pipeline compiles natively, so buffer sizes match
    TPU-bf16 byte-for-byte.  The dry-run never executes, so numerics are
    irrelevant; TPU builds use bf16 unchanged.
    """
    import dataclasses as _d

    import jax.numpy as _jnp

    def swap(dt):
        return _jnp.float16 if dt == _jnp.bfloat16 else dt

    return _d.replace(cfg, compute_dtype=swap(cfg.compute_dtype),
                      cache_dtype=swap(cfg.cache_dtype),
                      param_dtype=swap(cfg.param_dtype))


def _meter_variants(cfg):
    """Three shallow trip-count-1 configs (A=1 period, B=2, C=+tail)."""
    BIG = 1 << 30
    P = len(cfg.pattern)
    common = dict(kv_chunk=BIG, ssd_unroll=BIG)
    if cfg.enc_dec:
        A = dataclasses.replace(cfg, n_layers=1, **common)
        B = dataclasses.replace(cfg, n_layers=2, **common)
        return A, B, None, 1, cfg.n_layers
    A = dataclasses.replace(cfg, n_layers=P, **common)
    B = dataclasses.replace(cfg, pattern=cfg.pattern * 2, n_layers=2 * P,
                            **common)
    C = None
    if cfg.n_layers % P:
        tail = cfg.tail_specs
        C = dataclasses.replace(cfg, pattern=cfg.pattern + tail,
                                n_layers=P + len(tail), **common)
    return A, B, C, 1, cfg.n_periods


def _lower_cell(arch, shape_id, mesh, cfg, *, donate=True):
    """Lower+compile one cell for one config variant. Returns compiled."""
    import jax

    from repro.configs import SHAPES
    from repro.launch.steps import (abstract_opt_state, batch_logical,
                                    input_specs, make_decode_step,
                                    make_prefill_step, make_train_step)
    from repro.optim.adamw import OptState
    from repro.parallel.sharding import logical_to_spec
    from repro.runtime.elastic import specs_for_mesh
    from jax.sharding import NamedSharding

    model = _build(cfg)
    sh = SHAPES[shape_id]
    kind = sh["kind"]
    aparams, logical = model.abstract_params()
    param_sh = specs_for_mesh(logical, aparams, mesh, cfg.rules)
    specs = input_specs(arch, shape_id)
    blog = batch_logical(arch, shape_id)
    batch_sh = {k: NamedSharding(mesh, logical_to_spec(
        blog[k], specs[k].shape, mesh, rules=cfg.rules, name=k))
        for k in specs}

    with jax.set_mesh(mesh):
        if kind == "train":
            from repro.optim.schedule import cosine_schedule
            step = make_train_step(
                model, cfg,
                lr_fn=lambda s: cosine_schedule(
                    s, peak_lr=3e-4, warmup_steps=100, total_steps=10000),
                n_micro=cfg.n_micro)
            aopt = abstract_opt_state(aparams)
            rep = NamedSharding(mesh, logical_to_spec((), (), mesh))
            opt_sh = OptState(mu=param_sh, nu=param_sh, count=rep)
            jfn = jax.jit(step,
                          in_shardings=(param_sh, opt_sh, batch_sh),
                          out_shardings=(param_sh, opt_sh, None),
                          donate_argnums=(0, 1) if donate else ())
            lowered = jfn.lower(aparams, aopt, specs)
        elif kind == "prefill":
            step = make_prefill_step(model, cfg, max_len=sh["seq"] + 1)
            jfn = jax.jit(step, in_shardings=(param_sh, batch_sh))
            lowered = jfn.lower(aparams, specs)
        else:  # decode
            step = make_decode_step(model, cfg)
            acache = model.abstract_cache(sh["batch"], sh["seq"])
            clog = model.cache_logical(sh["batch"], sh["seq"])
            cache_sh = jax.tree.map(
                lambda lg, s: NamedSharding(mesh, logical_to_spec(
                    lg, s.shape, mesh, rules=cfg.rules, name="cache")),
                clog, acache,
                is_leaf=lambda t: isinstance(t, tuple) and all(
                    isinstance(a, (str, type(None))) for a in t))
            jfn = jax.jit(step,
                          in_shardings=(param_sh, cache_sh, batch_sh),
                          out_shardings=(None, None, cache_sh),
                          donate_argnums=(1,) if donate else ())
            lowered = jfn.lower(aparams, acache, specs)
        compiled = lowered.compile()
    return compiled


def _apply_opts(cfg, opt: str):
    """Hillclimb variants: comma-separated knobs, e.g.
    ``headpad16,remat=dots_no_batch,kvchunk=2048,capacity=1.0,seqshard``."""
    for tok in (opt or "").split(","):
        tok = tok.strip()
        if not tok:
            continue
        if tok.startswith("headpad"):
            cfg = dataclasses.replace(cfg, pad_heads_to=int(tok[7:]))
        elif tok.startswith("remat="):
            cfg = dataclasses.replace(cfg, remat=tok[6:])
        elif tok.startswith("kvchunk="):
            cfg = dataclasses.replace(cfg, kv_chunk=int(tok[8:]))
        elif tok.startswith("capacity="):
            cfg = dataclasses.replace(cfg, capacity_factor=float(tok[9:]))
        elif tok.startswith("micro="):
            cfg = dataclasses.replace(cfg, n_micro=int(tok[6:]))
        elif tok == "cachef8":
            import jax.numpy as _jnp
            cfg = dataclasses.replace(cfg,
                                      cache_dtype=_jnp.float8_e4m3fn)
        elif tok == "seqshard":
            # Megatron SP: residual stream's sequence axis over "model"
            # (process-global; each dry-run cell is its own subprocess)
            from repro.parallel.sharding import RULES
            RULES["seq_res"] = "model"
        elif tok.startswith("rules."):          # rules.expert=data
            k, v = tok[6:].split("=")
            rules = dict(cfg.rules or {})
            rules[k] = None if v == "none" else v
            cfg = dataclasses.replace(cfg, rules=rules)
        else:
            raise ValueError(f"unknown opt {tok!r}")
    return cfg


def run_cell(arch, shape_id, mesh_kind="single", *, meter=True,
             out_dir="artifacts/dryrun", opt=None):
    """Full dry-run of one cell; writes JSON; returns the record."""
    import jax

    from repro.configs import get_config, shape_applicable
    from repro.launch.mesh import make_production_mesh
    from repro.parallel import sharding as shmod

    rec = {"arch": arch, "shape": shape_id, "mesh": mesh_kind,
           "opt": opt or "", "time": time.time()}
    ok, reason = shape_applicable(arch, shape_id)
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__opt-{opt}" if opt else ""
    path = os.path.join(
        out_dir,
        f"{arch}__{shape_id}__{mesh_kind}{suffix}.json".replace("/", "_"))
    if not ok:
        rec.update(status="skipped", reason=reason)
        json.dump(rec, open(path, "w"), indent=1)
        return rec

    mesh = make_production_mesh(multi_pod=mesh_kind == "multi")
    cfg = _f16_standin(get_config(arch))
    if opt:
        cfg = _apply_opts(cfg, opt)
    try:
        shmod.fallback_log.clear()
        t0 = time.time()
        compiled = _lower_cell(arch, shape_id, mesh, cfg)
        rec["compile_s"] = round(time.time() - t0, 1)
        rec["memory"] = _mem_dict(compiled.memory_analysis())
        ca = cost_analysis_dict(compiled)
        rec["cost_raw"] = {k: float(ca.get(k, 0.0))
                           for k in ("flops", "bytes accessed")}
        rec["collectives_raw"] = collective_bytes(compiled.as_text())
        rec["fallbacks"] = sorted({(n, a, d, str(m))
                                   for n, a, d, m in shmod.fallback_log})
        rec["status"] = "ok"
        del compiled
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-4000:])
        json.dump(rec, open(path, "w"), indent=1)
        return rec

    if meter and mesh_kind == "single":
        try:
            A, B, C, _, n_periods = _meter_variants(cfg)
            res = {}
            for name, vcfg in (("A", A), ("B", B), ("C", C)):
                if vcfg is None:
                    continue
                comp = _lower_cell(arch, shape_id, mesh, vcfg)
                ca = cost_analysis_dict(comp)
                res[name] = {
                    "flops": float(ca.get("flops", 0.0)),
                    "bytes": float(ca.get("bytes accessed", 0.0)),
                    "wire": collective_bytes(comp.as_text())["wire_bytes"],
                }
                del comp
            body = {k: res["B"][k] - res["A"][k] for k in res["A"]}
            fixed = {k: 2 * res["A"][k] - res["B"][k] for k in res["A"]}
            tail = ({k: res["C"][k] - res["A"][k] for k in res["A"]}
                    if "C" in res else {k: 0.0 for k in res["A"]})
            n_rep = cfg.n_layers if cfg.enc_dec else n_periods
            total = {k: fixed[k] + n_rep * body[k] + tail[k]
                     for k in res["A"]}
            rec["metered"] = {"variants": res, "body": body, "fixed": fixed,
                              "tail": tail, "n_periods": n_rep,
                              "total": total}
        except Exception as e:
            rec["metered"] = {"status": "error",
                              "error": f"{type(e).__name__}: {e}",
                              "trace": traceback.format_exc()[-2000:]}
    json.dump(rec, open(path, "w"), indent=1)
    return rec


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-meter", action="store_true")
    ap.add_argument("--opt", default=None,
                    help="hillclimb knobs, e.g. headpad16,remat=full")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--jobs", type=int, default=2)
    args = ap.parse_args(argv)

    if args.all:
        from repro.configs import ARCH_IDS, SHAPES
        cells = [(a, s, m) for a in ARCH_IDS for s in SHAPES
                 for m in ("single", "multi")]
        procs, failures = [], []

        def drain(block=False):
            for p, cell in list(procs):
                if block:
                    p.wait()
                if p.poll() is not None:
                    procs.remove((p, cell))
                    if p.returncode != 0:
                        failures.append(cell)
                    print(("FAIL " if p.returncode else "ok   ")
                          + "%s %s %s" % cell, flush=True)

        for cell in cells:
            while len(procs) >= args.jobs:
                drain()
                time.sleep(2)
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", cell[0], "--shape", cell[1], "--mesh", cell[2],
                   "--out", args.out]
            if args.no_meter:
                cmd.append("--no-meter")
            procs.append((subprocess.Popen(cmd), cell))
        while procs:
            drain(block=True)
        print(f"done; {len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    rec = run_cell(args.arch, args.shape, args.mesh,
                   meter=not args.no_meter, out_dir=args.out, opt=args.opt)
    print(json.dumps({k: v for k, v in rec.items() if k != "trace"},
                     indent=1)[:2000])
    if rec["status"] == "error":
        print(rec.get("trace", ""), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
