#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip.  Not part of a run.

    python bench/control.py --workload <cell> --seeds 1,2,...,12 \
        [--what sound,control,half_batch,loss_altered,exchange,fp8] \
        [--fault-seeds 3]

The cell's step is built once and, for each seed, driven through the
benchmark's checked steps (``run.checked_steps``); the plain float32
reference follows the same steps.  Each reading is compared with the
reference by ``bench/check.py`` and judged against the cell's limits:

  sound         the program as the configuration states it: its readings
                set the lower end of each limit and have to be correct;
  control       the program with its weights gathered in INT4, the
                precision below the configuration's INT8 gathers, through
                its own path (``faults.int4_gathers``; the step is built a
                second time): it has to come out not correct;
  half_batch    the program with half of each batch left out, the mean
                taken over the rest (``faults.half_batch``);
  loss_altered  the program's loss off by 1 % where the step produces it;
  exchange      the program with the gradient exchange between chips left
                out (``faults.leave_out_exchange``; cells on more than one
                chip).  The step is built a second time;
  fp8           the reference computed in float8 put in the program's
                place: printed, and not held to fail (PERF.md says why).

The sound readings are taken on every seed, the others on the first
``--fault-seeds`` of them.  A state left unchanged reads 1 on
``change_gap`` by the measure itself and needs no run.  Each seed prints
one JSON line; the last line says whether every sound reading was
correct and every control and fault reading not.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

WHAT = ("sound", "control", "half_batch", "loss_altered", "exchange", "fp8")


def judged(mine, base, limits):
    from bench import check
    values, where = check.numbers(mine, base)
    ok, _ = check.judge(values, limits)
    return {"correct": ok, "numbers": values, "at": where}


def program_readings(prog, tr, cfg, seeds, wrap=None):
    """The program's checked-step readings for each seed, its state freed
    after each."""
    import jax

    from bench import run, tokens
    fn = prog.fn
    if wrap is not None:
        prog.fn = wrap(fn)
    out = {}
    try:
        for seed in seeds:
            gen = tokens.Tokens(tr, cfg["vocab"], seed)
            mine, state = run.checked_steps(prog, gen, seed)
            jax.block_until_ready(state)
            del state
            gc.collect()
            out[seed] = mine
    finally:
        prog.fn = fn
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default=",".join(WHAT))
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    from bench import reference, run, spec, tokens
    from bench.tests import faults
    cell = spec.cell(args.workload)
    devices = run.chips_or_exit(cell.chips)
    from repro.launch.train import enable_compile_cache
    enable_compile_cache()
    what = args.what.split(",")
    unknown = set(what) - set(WHAT)
    if unknown:
        raise SystemExit(f"unknown readings {sorted(unknown)}")
    seeds = [int(s) for s in args.seeds.split(",")]
    few = seeds[:args.fault_seeds]
    cfg, tr = cell.config, cell.traffic

    t = time.perf_counter()
    prog = run.Program(cell, devices)
    got = {}
    if "sound" in what:
        got["sound"] = program_readings(prog, tr, cfg, seeds)
    if "half_batch" in what and int(tr["global_batch"]) >= 2:
        got["half_batch"] = program_readings(prog, tr, cfg, few,
                                             faults.half_batch)
    if "loss_altered" in what:
        got["loss_altered"] = program_readings(prog, tr, cfg, few,
                                               faults.loss_altered)
    del prog
    crashed = {}        # a control or fault that crashes has failed

    def rebuilt(name, plant):
        try:
            with plant():
                prog = run.Program(cell, devices)
            got[name] = program_readings(prog, tr, cfg, few)
        except Exception as e:
            crashed[name] = f"{type(e).__name__}: {e}"[:2000]
            print(f"{name}: {crashed[name]}", file=sys.stderr, flush=True)

    if "control" in what:
        rebuilt("control", faults.int4_gathers)
    if "exchange" in what and cell.chips > 1:
        rebuilt("exchange", faults.exchange_left_out)
    print(f"program readings {time.perf_counter() - t:.1f} s",
          file=sys.stderr, flush=True)
    import jax
    jax.clear_caches()
    gc.collect()

    ref = reference.Reference(cfg, tr)
    fp8 = reference.Reference(cfg, tr, "fp8")
    verdict = True
    for seed in seeds:
        gen = tokens.Tokens(tr, cfg["vocab"], seed)
        batches = [gen.batch(k) for k in range(run.CHECK_STEPS)]
        t = time.perf_counter()
        base = ref.run(seed, batches)
        out = {"seed": seed, "reference_s": time.perf_counter() - t,
               "losses": base.losses}
        for name, by_seed in got.items():
            if seed in by_seed:
                out[name] = judged(by_seed[seed], base, cell.limits)
                out[name]["losses"] = by_seed[seed].losses
        if "fp8" in what and seed in few:
            f = fp8.run(seed, batches)
            out["fp8"] = judged(f, base, cell.limits)
            out["fp8"]["losses"] = f.losses
        for name, r in out.items():
            if isinstance(r, dict) and name != "fp8":
                verdict &= r["correct"] == (name == "sound")
        print(json.dumps(out), flush=True)
    print(json.dumps({"workload": cell.name, "limits": cell.limits,
                      "crashed": crashed,
                      "sound_correct_and_rest_not": verdict}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
