"""Tune the ejection-guarantee thresholds across a scenario zoo.

    PYTHONPATH=src python benchmarks/zoo_tune.py \
        --out benchmarks/zoo_thresholds.json

The pending-completion queue's ejection guarantee (docs/architecture.md)
has two thresholds: ``eject_age_threshold`` (a *traced* per-scenario
knob — varying it never splits a compile bucket) and ``req_timeout``
(a compiled constant — each value is its own bucket).  They were tuned
on the single ROADMAP wedge family; this harness closes that residual by
sweeping both across any set of zoo families (:mod:`repro.core.zoo`).

Sweep structure (this is why the whole thing is cheap):

* for each ``req_timeout`` value, ONE plan holds every (scenario x
  eject-age) variant — the age rides as ``SimState.knob_ej_age``, so a
  bucket of B scenarios x A ages compiles ONCE and runs as a batch of
  B*A lanes through :func:`repro.core.sweep.run_sweep`;
* the planner splits buckets only on mesh shape (and ``req_timeout``),
  so a full grid over Z zoo scenarios costs ``len(timeouts) x
  n_mesh_shapes`` compiles, not ``Z x A x T``.

Scoring: a config ``(req_timeout, eject_age_threshold)`` is *safe* when
every scenario finishes (no livelock abort, no cycle-cap overrun).  Among
safe configs the score is the mean per-scenario completion-cycle count
normalized by that scenario's best observed cycles (lower = faster).
The emitted JSON holds the full table, the current defaults' row, and a
``recommendation`` — with a stability bias: the defaults are kept unless
a challenger is more than ``--flip-margin`` (default 1%) faster.

``--smoke`` runs a tiny slice (patterns-tiny, one timeout, two ages),
self-checks the emitted JSON shape, and exits — the CI ``zoo-smoke``
job's second half.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import platform
import sys
import time

sys.path.insert(0, "src")

from repro.core import engine                              # noqa: E402

engine.expose_host_devices()   # before anything imports jax

from repro.core.config import SimConfig                    # noqa: E402
from repro.core.engine import Scenario                     # noqa: E402
from repro.core.zoo import expand_zoo                      # noqa: E402

DEFAULT_ZOOS = ("patterns-small", "hotspot-stress", "patterns-rates",
                "wedge")
DEFAULTS = {"eject_age_threshold": SimConfig.eject_age_threshold,
            "req_timeout": SimConfig.req_timeout}


def run_grid(base_scenarios, ej_ages, timeouts, max_cycles, chunk):
    """Run every (scenario, age, timeout) variant; returns
    ``{(timeout, age): [stats per base scenario]}``.

    One :func:`repro.core.engine.plan_and_run` call per timeout carries
    all age variants as traced knobs (ONE compile per mesh shape)."""
    table = {}
    for tmo in timeouts:
        variants = [
            Scenario(cfg=dataclasses.replace(sc.cfg, req_timeout=tmo,
                                             eject_age_threshold=age),
                     app=sc.app, seed=sc.seed,
                     refs_per_core=sc.refs_per_core)
            for age in ej_ages for sc in base_scenarios]
        t0 = time.time()
        res = engine.plan_and_run(variants, max_cycles=max_cycles,
                                  chunk=chunk)
        print(f"req_timeout={tmo}: {len(variants)} variant runs in "
              f"{time.time() - t0:.1f}s", file=sys.stderr)
        for ai, age in enumerate(ej_ages):
            lo = ai * len(base_scenarios)
            table[(tmo, age)] = res[lo:lo + len(base_scenarios)]
    return table


def score(table, base_scenarios):
    """Per-config rows + recommendation inputs from the raw grid."""
    nsc = len(base_scenarios)
    # best observed completion cycles per base scenario (finished runs)
    best = [None] * nsc
    for res in table.values():
        for i, st in enumerate(res):
            if st.get("finished"):
                c = st["cycles"]
                best[i] = c if best[i] is None else min(best[i], c)
    rows = []
    for (tmo, age), res in table.items():
        unfinished = [i for i, st in enumerate(res)
                      if not st.get("finished")]
        aborted = [i for i, st in enumerate(res) if "aborted" in st]
        norms = [st["cycles"] / best[i] for i, st in enumerate(res)
                 if st.get("finished") and best[i]]
        rows.append({
            "req_timeout": tmo,
            "eject_age_threshold": age,
            "finished": nsc - len(unfinished),
            "unfinished": len(unfinished),
            "aborted": len(aborted),
            "unfinished_scenarios": [
                f"{base_scenarios[i].cfg.rows}x{base_scenarios[i].cfg.cols}"
                f":{base_scenarios[i].app}:{base_scenarios[i].seed}"
                for i in unfinished],
            "mean_norm_cycles": (round(sum(norms) / len(norms), 4)
                                 if norms else None),
            "total_drops": sum(st.get("send_drop", 0) for st in res),
        })
    rows.sort(key=lambda r: (r["req_timeout"], r["eject_age_threshold"]))
    return rows


def recommend(rows, flip_margin):
    """Pick the recommended config: safest first, then fastest, with a
    stability bias of ``flip_margin`` toward the current defaults."""
    safe = [r for r in rows if r["unfinished"] == 0
            and r["mean_norm_cycles"] is not None]
    if not safe:
        return None, False, "no config finished every scenario"
    best = min(safe, key=lambda r: r["mean_norm_cycles"])
    in_grid = [r for r in rows
               if r["req_timeout"] == DEFAULTS["req_timeout"]
               and r["eject_age_threshold"]
               == DEFAULTS["eject_age_threshold"]]
    if not in_grid:
        # the defaults were never measured: recommend the best swept
        # config but claim no authority to flip
        return best, False, (
            f"current defaults {DEFAULTS} were not in the swept grid; "
            "best swept config reported, no basis to flip")
    cur = [r for r in in_grid if r in safe]
    if cur:
        gain = cur[0]["mean_norm_cycles"] - best["mean_norm_cycles"]
        if gain <= flip_margin:
            return cur[0], False, (
                f"defaults are safe and within {flip_margin:.0%} of the "
                f"best config (gain {gain:.4f}); keeping them")
        return best, True, (
            f"best config beats the safe defaults by {gain:.4f} "
            f"normalized cycles (> {flip_margin:.0%} margin)")
    return best, True, "current defaults left scenarios unfinished"


def main() -> int:
    engine.enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--zoo", default=",".join(DEFAULT_ZOOS),
                    help="comma list of zoo family specs to tune over")
    ap.add_argument("--ej-ages", default="0,2,4,8,16",
                    help="comma list of eject_age_threshold values")
    ap.add_argument("--timeouts", default="64,256,1024",
                    help="comma list of req_timeout values")
    ap.add_argument("--max-cycles", type=int, default=100_000)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--flip-margin", type=float, default=0.01,
                    help="minimum normalized-cycles gain before the "
                         "recommendation moves off the current defaults")
    ap.add_argument("--out", default=None,
                    help="write the recommendation JSON here")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny slice + self-check of the emitted JSON "
                         "(CI zoo-smoke)")
    args = ap.parse_args()

    if args.smoke:
        zoos = ["patterns-tiny:refs=8,seeds=0"]
        ej_ages, timeouts = [0, 8], [256]
        args.max_cycles = min(args.max_cycles, 50_000)
    else:
        zoos = [z for z in args.zoo.split(",") if z.strip()]
        ej_ages = [int(x) for x in args.ej_ages.split(",")]
        timeouts = [int(x) for x in args.timeouts.split(",")]

    base_scenarios = []
    for z in zoos:
        base_scenarios.extend(expand_zoo(z))
    print(f"zoo: {zoos} -> {len(base_scenarios)} scenarios x "
          f"{len(ej_ages)} ages x {len(timeouts)} timeouts",
          file=sys.stderr)

    table = run_grid(base_scenarios, ej_ages, timeouts,
                     args.max_cycles, args.chunk)
    rows = score(table, base_scenarios)
    rec, flip, why = recommend(rows, args.flip_margin)

    import jax
    payload = {
        "meta": {
            "zoos": zoos,
            "n_scenarios": len(base_scenarios),
            "ej_ages": ej_ages,
            "timeouts": timeouts,
            "max_cycles": args.max_cycles,
            "host": platform.node(),
            "platform": jax.devices()[0].platform,
            "devices": len(jax.devices()),
        },
        "defaults": DEFAULTS,
        "table": rows,
        "recommendation": (None if rec is None else {
            "req_timeout": rec["req_timeout"],
            "eject_age_threshold": rec["eject_age_threshold"],
            "mean_norm_cycles": rec["mean_norm_cycles"],
        }),
        "flip_defaults": flip,
        "rationale": why,
    }
    text = json.dumps(payload, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.out}", file=sys.stderr)
    print(text)

    if args.smoke:
        # self-check: the harness must emit a well-formed recommendation
        assert payload["table"], "empty table"
        for r in payload["table"]:
            for k in ("req_timeout", "eject_age_threshold", "finished",
                      "unfinished", "aborted", "mean_norm_cycles"):
                assert k in r, (k, r)
        assert payload["recommendation"] is not None, payload["rationale"]
        assert isinstance(payload["flip_defaults"], bool)
        print("SMOKE OK", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
