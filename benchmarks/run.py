"""Benchmark orchestrator: one module per paper table/figure.
Each prints CSV rows (also written to bench_out/<name>.csv); a final pass
folds everything into machine-readable bench_out/BENCH_bfs.json so the perf
trajectory (TEPS, bytes-per-edge per fold codec, per-phase times) is
trackable across PRs.

  fig3   weak scaling (TEPS vs devices, scale/device fixed)
  fig4   strong scaling (fixed graph; minimal 1x1-vs-2x2 sweep in smoke)
  fig5/6 per-level traversal counters from the in-program telemetry trace
         (frontier/scanned/folded/wire/direction; DESIGN.md sec. 13) + fold
         wire bytes before/after the single-message overhaul per codec
         (DESIGN.md sec. 10)
  fig7   1D baseline (degenerate 1xP grid of the shared engine) vs 2D
  fold   list/bitmap/delta fold codec head-to-head (+ equality check)
  fig8/t2 atomic-style vs sort/compact expansion
  table3 real-world graph analogs
  direction top-down vs bottom-up vs adaptive sweep + per-level alpha/beta
         decisions and bottom-up phase times (DESIGN.md sec. 11)
  exchange flat vs butterfly fold routes on a 1x4 column grid: per-level
         message/byte totals from the LevelTrace msgs channel, the
         log2(C)-vs-(C-1) message crossover, bit-identity across
         strategies (DESIGN.md sec. 14)

CLI:
  --serve     run ONLY the serve-load suite (benchmarks/serve_load.py: a
              GraphServer under an offered-load sweep with mixed
              BFS/CC/SSSP/multi-BFS traffic on 2x2 simulated devices) and
              gate its bench_out/BENCH_serve.json: schema, >= 3 load
              points, all bit-exact, zero failed queries, mean batch
              occupancy > 1 at the highest offered load, and the fault
              drill failing exactly the poisoned request -- never
              wall-clock
  --obs       run ONLY the telemetry contract suite (benchmarks/obs_bench.py)
              and gate its bench_out/BENCH_obs.json: schema, trace-vs-
              recomputation agreement per codec, telemetry on/off
              bit-identity, no-retrace trace counts, serve spans + events,
              and traced-sweep overhead <= 5% (a same-host ratio, the only
              timing-derived gate; never a wall-clock floor)
  --fault     run ONLY the fault-drill suite (benchmarks/fault_drill.py:
              the device-loss drill matrix of DESIGN.md sec. 15 on 2x2
              simulated devices) and gate its bench_out/BENCH_fault.json:
              every drill completes, zero lost queries, recovered outputs
              bit-identical (Graph500-valid preds after a shrink), at
              least one drill actually shrank the grid, recovery latency
              recorded as a number, and the no-retrace proof that
              fault_tolerance=False builds nothing -- never wall-clock
  --scale N   force every honoring suite to graph scale N (REPRO_BENCH_SCALE)
  --smoke     reduced CI suite list (fold codecs on 2x2 simulated devices,
              strong-scaling mini sweep, per-level breakdown + fold wire
              bytes, algos sweep, direction sweep, exchange crossover)
              with fewer roots/iters; the bit-exactness and schema
              gates still run in full and a violation exits non-zero (the
              regression gates are on correctness counters and wire-byte
              accounting, never on wall-clock)
"""
import argparse
import json
import os
import sys
import time
import traceback

# runnable as `python benchmarks/run.py` from anywhere: the --serve/--obs/
# --fault suites import each other through the `benchmarks` namespace
# package at the repo root
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks import common


def _f(x):
    try:
        return float(x)
    except (TypeError, ValueError):
        return None


def write_bench_json() -> None:
    """Aggregate whatever CSVs exist into bench_out/BENCH_bfs.json."""
    from benchmarks.common import emit_json, read_csv

    def teps_rows(name):
        return [
            {"variant": r.get("variant"), "grid": f'{r.get("R")}x{r.get("C")}',
             "scale": _f(r.get("scale")), "ef": _f(r.get("ef")),
             "harmonic_TEPS": _f(r.get("harmonic_TEPS")),
             "mean_s": _f(r.get("mean_s")), "levels": _f(r.get("levels")),
             "fold": r.get("fold"),
             "fold_bytes_per_edge": _f(r.get("fold_bytes_per_edge")),
             # the session API's amortised view: all roots in ONE compiled
             # program (GraphSession.bfs(roots_batch)); batched_harmonic is
             # the harmonic mean over the SAME count_component_edges
             # numerators as harmonic_TEPS, over sweep_s / n_roots
             "batched_sweep_s": _f(r.get("batched_sweep_s")),
             "amortised_TEPS": _f(r.get("amortised_TEPS")),
             "batched_harmonic_TEPS": _f(r.get("batched_harmonic_TEPS"))}
            for r in read_csv(name)]

    codecs = {}
    for r in read_csv("fold_codecs"):
        codecs[r["fold"]] = {
            "harmonic_TEPS": _f(r.get("harmonic_TEPS")),
            "bytes_per_edge": _f(r.get("fold_bytes_per_edge")),
            "batched_sweep_s": _f(r.get("batched_sweep_s")),
            "amortised_TEPS": _f(r.get("amortised_TEPS")),
            "batched_harmonic_TEPS": _f(r.get("batched_harmonic_TEPS")),
            "lvl_sum": r.get("lvl_sum"), "pred_sum": r.get("pred_sum"),
            "scale": _f(r.get("scale")), "grid": f'{r.get("R")}x{r.get("C")}'}

    # per-LEVEL traversal counters of a real search (v7: read from the
    # in-program LevelTrace -- work counters, not wall times; fed by
    # benchmarks/bfs_breakdown.py through workers/trace_worker.py)
    phases = [
        {"scale": _f(r.get("scale")), "grid": f'{r.get("R")}x{r.get("C")}',
         "level": _f(r.get("level")), "frontier": _f(r.get("frontier")),
         "scanned": _f(r.get("scanned")), "folded": _f(r.get("folded")),
         "wire_bytes": _f(r.get("wire_bytes")), "msgs": _f(r.get("msgs")),
         "dir": _f(r.get("dir"))}
        for r in read_csv("fig5_6_breakdown")]

    # fold wire-byte accounting per codec, summed over the measured levels:
    # PR-4 layout (separate count collective + dense value channel) vs the
    # fused single message (header word + count-proportional value prefix)
    fold_wire = {}
    for r in read_csv("fold_wire"):
        key = (r["codec"], f'{r.get("R")}x{r.get("C")}')
        agg = fold_wire.setdefault(key, {
            "codec": r["codec"], "grid": key[1], "scale": _f(r.get("scale")),
            "levels": 0, "folded": 0,
            "set_msgs_before": int(r["set_msgs_before"]),
            "value_msgs_before": int(r["value_msgs_before"]),
            "msgs_after": int(r["msgs_after"]),
            "set_bytes_before": 0, "set_bytes_after": 0,
            "value_bytes_dense": 0, "value_bytes_sent": 0,
            "edges": int(r["edges"])})
        agg["levels"] += 1
        agg["folded"] += int(r["folded"])
        for k in ("set_bytes_before", "set_bytes_after", "value_bytes_dense",
                  "value_bytes_sent"):
            agg[k] += int(r[k])
    for agg in fold_wire.values():
        e = max(agg["edges"], 1)
        agg["value_bytes_per_edge_dense"] = agg["value_bytes_dense"] / e
        agg["value_bytes_per_edge_sent"] = agg["value_bytes_sent"] / e
    fold_wire = [fold_wire[k] for k in sorted(fold_wire)]

    # the direction dimension (v6): per-mode whole-search times with
    # bit-equality checksums, the adaptive per-level decision trace, and the
    # replayed bottom-up phase time per level (bfs_expansion_variants.
    # direction_sweep; DESIGN.md sec. 11)
    dir_rows = read_csv("direction_sweep")
    direction = {}
    for r in dir_rows:
        direction[r["mode"]] = {
            "scale": _f(r.get("scale")), "grid": f'{r.get("R")}x{r.get("C")}',
            "mean_s": _f(r.get("mean_s")), "levels": _f(r.get("levels")),
            "lvl_sum": r.get("lvl_sum"), "pred_sum": r.get("pred_sum"),
            "dirs": [int(x) for x in r.get("dirs", "").split("|")
                     if x not in ("", "-1")]}
    direction_levels = [
        {"level": _f(r.get("level")), "frontier": _f(r.get("frontier")),
         "dir": _f(r.get("dir")), "bottomup_s": _f(r.get("bottomup_s"))}
        for r in read_csv("direction_levels")]

    # the exchange dimension (v8): flat vs butterfly fold routes on a 1xC
    # column grid -- per-level msgs/bytes from the LevelTrace, aggregated
    # to per-strategy totals so the message crossover (log2(C) vs C-1) is
    # trackable across PRs (benchmarks/bfs_exchange.py; DESIGN.md sec. 14)
    ex_rows = read_csv("exchange")
    exchange = {}
    for r in ex_rows:
        key = (r["strategy"], r["codec"])
        agg = exchange.setdefault(key, {
            "strategy": r["strategy"], "codec": r["codec"],
            "C": int(r["C"]), "scale": _f(r.get("scale")),
            "levels": 0, "total_msgs": 0, "total_wire_bytes": 0,
            "folded": 0})
        agg["levels"] += 1
        agg["total_msgs"] += int(r["msgs"])
        agg["total_wire_bytes"] += int(r["wire_bytes"])
        agg["folded"] += int(r["folded"])
    exchange = [exchange[k] for k in sorted(exchange)]
    # bit-identity across strategies is asserted INSIDE bfs_exchange.py on
    # the raw checksums; the JSON records whether the comparison ran and
    # whether every (codec, level) row pair agreed on frontier/folded
    by_cell = {}
    for r in ex_rows:
        by_cell.setdefault((r["codec"], r["level"]), {})[r["strategy"]] = \
            (r.get("frontier"), r.get("folded"))
    exchange_agree = (all(len(set(cell.values())) == 1
                          for cell in by_cell.values())
                      if ex_rows else None)

    out = {
        "schema": "BENCH_bfs/v9",   # v9: - expand_paths (one scan
                                    # implementation, nothing to compare);
                                    # v8: + exchange (flat-vs-butterfly
                                    # message/byte totals + agreement) and
                                    # the msgs trace channel in phases;
                                    # v7: phases = in-program LevelTrace
                                    # counters instead of host-replay times
        "teps": {
            "weak_scaling": teps_rows("fig3_weak_scaling"),
            "strong_scaling": teps_rows("fig4_strong_scaling"),
            "one_d_vs_two_d": teps_rows("fig7_1d_vs_2d"),
        },
        "fold_codecs": codecs,
        # null (not true) when no comparison ran -- an absent suite must not
        # read as a passed bit-exactness gate
        "codecs_agree": (len({(v["lvl_sum"], v["pred_sum"])
                              for v in codecs.values()}) == 1
                         if codecs else None),
        "phases": phases,
        "fold_wire": fold_wire,
        "direction": direction,
        "direction_levels": direction_levels,
        # null (not true) when the sweep did not run: an absent suite must
        # not read as a passed bit-equality gate
        "direction_agree": (
            len({(v["lvl_sum"], v["pred_sum"]) for v in direction.values()})
            == 1 if direction else None),
        "exchange": exchange,
        "exchange_agree": exchange_agree,
    }
    path = emit_json(out, "BENCH_bfs")
    print(f"\nwrote {path}")


def validate_serve() -> list:
    """Gates over bench_out/BENCH_serve.json (the --serve mode artifact).

    Correctness and coalescing-shape gates only -- zero failed queries,
    every point bit-identical to direct GraphSession calls, the highest
    offered-load point actually batching (mean occupancy > 1), and the
    fault drill failing exactly its one poisoned request -- NEVER
    wall-clock (the p50/p99 columns are trajectory data, not gates).
    """
    errors = []
    p = os.path.join(common.OUT_DIR, "BENCH_serve.json")
    if not os.path.exists(p):
        return ["BENCH_serve.json missing"]
    try:
        with open(p) as f:
            serve = json.load(f)
    except json.JSONDecodeError as e:
        return [f"BENCH_serve.json: invalid JSON ({e})"]
    if serve.get("schema") != "BENCH_serve/v1":
        errors.append(f"BENCH_serve schema {serve.get('schema')!r} != "
                      f"'BENCH_serve/v1'")
    for key in ("load", "fault", "aot_cache", "tenants"):
        if key not in serve:
            errors.append(f"BENCH_serve missing key {key!r}")
    load = serve.get("load") or []
    if len(load) < 3:
        errors.append(f"BENCH_serve: {len(load)} offered-load points < 3")
    for p_ in load:
        if p_.get("bitexact") is not True:
            errors.append(f"BENCH_serve: point offered_qps="
                          f"{p_.get('offered_qps')} not bit-exact")
        if p_.get("n_failed"):
            errors.append(f"BENCH_serve: {p_['n_failed']} failed queries at "
                          f"offered_qps={p_.get('offered_qps')}")
    if load:
        top = max(load, key=lambda p_: p_.get("offered_qps") or 0)
        if not ((top.get("mean_occupancy") or 0) > 1):
            errors.append(
                f"BENCH_serve: highest offered load did not coalesce "
                f"(mean_occupancy={top.get('mean_occupancy')} <= 1)")
    drill = serve.get("fault")
    if not drill:
        errors.append("BENCH_serve: fault drill missing")
    else:
        if drill.get("injected") != 1 or drill.get("failed") != 1:
            errors.append(f"BENCH_serve: fault drill must fail exactly the "
                          f"poisoned request, got {drill}")
        if not drill.get("ok_after"):
            errors.append(f"BENCH_serve: no queries served after the fault "
                          f"({drill})")
    if not serve.get("aot_cache"):
        errors.append("BENCH_serve: aot_cache section empty")
    if len(serve.get("tenants") or {}) < 2:
        errors.append("BENCH_serve: expected >= 2 tenants in accounting")
    return errors


def validate_obs() -> list:
    """Gates over bench_out/BENCH_obs.json (the --obs mode artifact).

    Correctness gates: trace-vs-recomputation agreement for every codec,
    telemetry on/off bit-identity, the no-retrace trace-count proof, serve
    spans + a non-empty event log, a rendering Prometheus endpoint -- plus
    the one timing-DERIVED gate in CI: the traced batched sweep may cost at
    most 5% over the untraced one (medians of alternating repeats, with a
    10ms absolute epsilon for timer noise).  That is a same-host ratio of
    the same program, not a wall-clock floor.
    """
    errors = []
    p = os.path.join(common.OUT_DIR, "BENCH_obs.json")
    if not os.path.exists(p):
        return ["BENCH_obs.json missing"]
    try:
        with open(p) as f:
            obs = json.load(f)
    except json.JSONDecodeError as e:
        return [f"BENCH_obs.json: invalid JSON ({e})"]
    if obs.get("schema") != "BENCH_obs/v1":
        errors.append(f"BENCH_obs schema {obs.get('schema')!r} != "
                      f"'BENCH_obs/v1'")
    agreement = obs.get("agreement") or {}
    if len(agreement) < 3:
        errors.append(f"BENCH_obs: agreement covers {len(agreement)} codecs "
                      f"< 3")
    for codec, checks in agreement.items():
        for name, ok in checks.items():
            if ok is not True:
                errors.append(f"BENCH_obs: {codec} trace {name} != true "
                              f"(trace disagrees with recomputation)")
    if obs.get("direction_agreement") is not True:
        errors.append("BENCH_obs: trace.direction disagrees with the "
                      "engine's directions output")
    bitexact = obs.get("bitexact") or {}
    if len(bitexact) < 3:
        errors.append(f"BENCH_obs: bitexact covers {len(bitexact)} codecs "
                      f"< 3")
    for codec, ok in bitexact.items():
        if ok is not True:
            errors.append(f"BENCH_obs: telemetry on/off NOT bit-identical "
                          f"for codec {codec}")
    for codec, tc in (obs.get("trace_counts") or {}).items():
        if tc.get("after_first_sweep") != tc.get("after_second_sweep"):
            errors.append(f"BENCH_obs: {codec} retraced on a repeat sweep "
                          f"({tc})")
    if not obs.get("trace_counts"):
        errors.append("BENCH_obs: trace_counts section empty")
    ov = obs.get("overhead") or {}
    frac, on, off = (ov.get("overhead_frac"), ov.get("on_median_s"),
                     ov.get("off_median_s"))
    if frac is None or on is None or off is None:
        errors.append(f"BENCH_obs: overhead section incomplete ({ov})")
    elif frac > 0.05 and (on - off) > 0.010:
        errors.append(f"BENCH_obs: traced sweep overhead {frac:.1%} > 5% "
                      f"(on={on:.4f}s off={off:.4f}s)")
    spans = obs.get("spans") or {}
    if spans.get("ok") is not True:
        errors.append("BENCH_obs: serve request-trace spans malformed")
    if not spans.get("n_events"):
        errors.append("BENCH_obs: serve event log recorded no events")
    if spans.get("prometheus_ok") is not True:
        errors.append("BENCH_obs: Prometheus exposition missing expected "
                      "series")
    return errors


def validate_fault() -> list:
    """Gates over bench_out/BENCH_fault.json (the --fault mode artifact).

    Correctness gates only: every drill in the matrix completes ok with
    zero lost queries, recovered outputs bit-identical where that is the
    contract (and Graph500-valid preds where it is not -- BFS after a
    shrink), at least one drill actually moved to a smaller grid, elastic
    drills RECORD their recovery latency (a number, never gated), and the
    no-retrace section proves `fault_tolerance=False` builds zero
    segmented programs and stays bit-identical / cache-resident.
    """
    errors = []
    p = os.path.join(common.OUT_DIR, "BENCH_fault.json")
    if not os.path.exists(p):
        return ["BENCH_fault.json missing"]
    try:
        with open(p) as f:
            fault = json.load(f)
    except json.JSONDecodeError as e:
        return [f"BENCH_fault.json: invalid JSON ({e})"]
    if fault.get("schema") != "BENCH_fault/v1":
        errors.append(f"BENCH_fault schema {fault.get('schema')!r} != "
                      f"'BENCH_fault/v1'")
    drills = fault.get("drills") or []
    if len(drills) < 20:
        errors.append(f"BENCH_fault: {len(drills)} drills < 20 (the "
                      "standard matrix)")
    runners = {d.get("runner") for d in drills}
    for need in ("session", "elastic", "serve"):
        if need not in runners:
            errors.append(f"BENCH_fault: no {need!r}-runner drill ran")
    shrunk = 0
    for d in drills:
        name = d.get("name", "?")
        if d.get("ok") is not True:
            errors.append(f"BENCH_fault[{name}]: ok != true "
                          f"(error={d.get('error')})")
        if d.get("lost_queries"):
            errors.append(f"BENCH_fault[{name}]: lost "
                          f"{d['lost_queries']} queries")
        if d.get("bit_identical") is False:
            errors.append(f"BENCH_fault[{name}]: recovered output NOT "
                          "bit-identical")
        if d.get("pred_valid") is False:
            errors.append(f"BENCH_fault[{name}]: recovered BFS preds "
                          "fail Graph500 validation")
        if d.get("grid_after") != d.get("grid_before"):
            shrunk += 1
        if d.get("runner") == "elastic" and not isinstance(
                d.get("time_to_first_resumed_level_s"), (int, float)):
            errors.append(f"BENCH_fault[{name}]: recovery latency not "
                          "recorded")
    if not shrunk:
        errors.append("BENCH_fault: no drill actually shrank the grid")
    nr = fault.get("no_retrace") or {}
    if nr.get("ft_off_segmented_programs") != 0:
        errors.append(f"BENCH_fault: fault_tolerance=False built "
                      f"{nr.get('ft_off_segmented_programs')} segmented "
                      "programs (expected 0)")
    if nr.get("after_first_sweep") != nr.get("after_second_sweep"):
        errors.append(f"BENCH_fault: repeat sweep retraced ({nr})")
    if nr.get("ft_on_off_bitexact") is not True:
        errors.append("BENCH_fault: FT on/off outputs NOT bit-identical")
    return errors


def validate_bench(smoke: bool) -> list:
    """Schema + correctness-counter gates over the emitted JSON artifacts.

    Returns a list of violation strings (empty = pass).  Gates correctness
    (codec / direction bit-exactness, schema shape), NEVER wall-clock.
    In smoke mode the smoke suites' sections are additionally REQUIRED, so
    a silently-skipped suite cannot read as a pass.
    """
    errors = []

    def load(name):
        p = os.path.join(common.OUT_DIR, f"{name}.json")
        if not os.path.exists(p):
            return None
        try:
            with open(p) as f:
                return json.load(f)
        except json.JSONDecodeError as e:
            errors.append(f"{name}.json: invalid JSON ({e})")
            return None

    bfs = load("BENCH_bfs")
    if bfs is None:
        errors.append("BENCH_bfs.json missing")
    else:
        if bfs.get("schema") != "BENCH_bfs/v9":
            errors.append(f"BENCH_bfs schema {bfs.get('schema')!r} != "
                          f"'BENCH_bfs/v9'")
        for key in ("teps", "fold_codecs", "codecs_agree", "phases",
                    "fold_wire", "direction", "direction_levels",
                    "direction_agree", "exchange", "exchange_agree"):
            if key not in bfs:
                errors.append(f"BENCH_bfs missing key {key!r}")
        if bfs.get("codecs_agree") is False:
            errors.append("fold codecs disagree on levels/preds "
                          "(codecs_agree = false)")
        if bfs.get("exchange_agree") is False:
            errors.append("flat vs butterfly per-level counters disagree "
                          "(exchange_agree = false)")
        # the butterfly must strictly undercut flat on per-level message
        # count whenever the exchange suite ran (log2(C) < C-1 at C >= 4);
        # wire-byte totals are trajectory data, never gated on magnitude
        ex = bfs.get("exchange") or []
        ex_msgs = {}
        for agg in ex:
            ex_msgs.setdefault(agg.get("codec"), {})[agg.get("strategy")] \
                = agg.get("total_msgs")
        for codec, per in ex_msgs.items():
            mf, mb = per.get("flat"), per.get("butterfly")
            if mf is not None and mb is not None and not (mb < mf):
                errors.append(f"exchange[{codec}]: butterfly msgs {mb} !< "
                              f"flat msgs {mf}")
        if bfs.get("direction_agree") is False:
            errors.append("direction modes disagree on levels/preds "
                          "(direction_agree = false)")
        # the compressed value channel must never exceed the PR-4
        # dense-channel baseline, and must STRICTLY undercut it for bitmap
        # (the codec the dense channel defeated hardest) whenever the
        # fold-wire suite ran
        for agg in bfs.get("fold_wire") or []:
            sent = agg.get("value_bytes_sent", 0)
            dense = agg.get("value_bytes_dense", 0)
            strict = agg.get("codec") == "bitmap"
            if (sent >= dense) if strict else (sent > dense):
                errors.append(
                    f"{agg.get('codec')} value-fold bytes not "
                    f"{'below' if strict else 'within'} the dense-channel "
                    f"baseline: sent={sent} vs dense={dense} "
                    f"(grid {agg.get('grid')})")
        if smoke:
            if not bfs.get("fold_codecs"):
                errors.append("smoke: fold_codecs section empty")
            if not bfs.get("phases"):
                errors.append("smoke: phases section empty")
            for row in bfs.get("phases") or []:
                if not (row.get("wire_bytes") or 0) > 0:
                    errors.append(f"smoke: phases row without trace wire "
                                  f"bytes: {row}")
                    break
            if not bfs.get("fold_wire"):
                errors.append("smoke: fold_wire section empty")
            if not any(c.get("codec") == "bitmap"
                       for c in bfs.get("fold_wire") or []):
                errors.append("smoke: fold_wire has no bitmap entry")
            if not (bfs.get("teps") or {}).get("strong_scaling"):
                errors.append("smoke: teps.strong_scaling empty")
            dr = bfs.get("direction") or {}
            for mode in ("False", "adaptive", "bottomup"):
                if mode not in dr:
                    errors.append(f"smoke: direction[{mode!r}] missing")
            if not bfs.get("direction_levels"):
                errors.append("smoke: direction_levels section empty")
            if not bfs.get("exchange"):
                errors.append("smoke: exchange section empty")
            if not any(a.get("strategy") == "butterfly"
                       for a in bfs.get("exchange") or []):
                errors.append("smoke: exchange has no butterfly entry")
            # the adaptive heuristic must actually flip at the smoke scale:
            # at least one top-down AND one bottom-up level
            ad = (dr.get("adaptive") or {}).get("dirs") or []
            if not (0 in ad and 1 in ad):
                errors.append(f"smoke: adaptive sweep exercised only one "
                              f"direction (dirs={ad})")

    algos = load("BENCH_algos")
    if algos is None:
        if smoke:
            errors.append("smoke: BENCH_algos.json missing")
    else:
        if algos.get("schema") != "BENCH_algos/v1":
            errors.append(f"BENCH_algos schema {algos.get('schema')!r} != "
                          f"'BENCH_algos/v1'")
        for name, res in (algos.get("algos") or {}).items():
            if res.get("codecs_agree") is not True:
                errors.append(f"BENCH_algos[{name!r}]: codecs_agree != true")
        if smoke and not algos.get("algos"):
            errors.append("smoke: BENCH_algos has no algos")
    return errors


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scale", type=int, default=None,
                    help="force graph scale for suites that honor it")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced CI suite list; correctness gates in full")
    ap.add_argument("--serve", action="store_true",
                    help="run only the serve-load suite and gate "
                         "BENCH_serve.json")
    ap.add_argument("--obs", action="store_true",
                    help="run only the telemetry contract suite and gate "
                         "BENCH_obs.json")
    ap.add_argument("--fault", action="store_true",
                    help="run only the fault-drill matrix and gate "
                         "BENCH_fault.json")
    args = ap.parse_args(argv)
    if args.scale is not None:
        os.environ["REPRO_BENCH_SCALE"] = str(args.scale)
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"

    if args.fault:
        from benchmarks import fault_drill
        print("\n=== fault_drill ===")
        t0 = time.time()
        try:
            fault_drill.main()
            print(f"--- fault_drill done in {time.time() - t0:.0f}s")
        except Exception:
            print(f"--- fault_drill FAILED:"
                  f"\n{traceback.format_exc()[-1500:]}")
            sys.exit(1)
        errors = validate_fault()
        for e in errors:
            print(f"VALIDATION: {e}")
        if errors:
            sys.exit(1)
        print("fault validation OK")
        return

    if args.obs:
        from benchmarks import obs_bench
        print("\n=== obs_bench ===")
        t0 = time.time()
        try:
            obs_bench.main()
            print(f"--- obs_bench done in {time.time() - t0:.0f}s")
        except Exception:
            print(f"--- obs_bench FAILED:\n{traceback.format_exc()[-1500:]}")
            sys.exit(1)
        errors = validate_obs()
        for e in errors:
            print(f"VALIDATION: {e}")
        if errors:
            sys.exit(1)
        print("obs validation OK")
        return

    if args.serve:
        from benchmarks import serve_load
        print("\n=== serve_load ===")
        t0 = time.time()
        try:
            serve_load.main()
            print(f"--- serve_load done in {time.time() - t0:.0f}s")
        except Exception:
            print(f"--- serve_load FAILED:\n{traceback.format_exc()[-1500:]}")
            sys.exit(1)
        errors = validate_serve()
        for e in errors:
            print(f"VALIDATION: {e}")
        if errors:
            sys.exit(1)
        print("serve validation OK")
        return

    # (suite label, "module:function", CSV name(s) the suite emits).  Each
    # suite runs in a worker process of its own (workers/suite_worker.py),
    # so this parent never imports JAX and a chip is held by one process
    suites = [
        ("algos_sweep", "algos_sweep:main", "algos_sweep"),
        ("fig3_weak_scaling", "bfs_weak_scaling:main", "fig3_weak_scaling"),
        ("fig4_strong_scaling", "bfs_strong_scaling:main",
         "fig4_strong_scaling"),
        ("fig5_6_breakdown", "bfs_breakdown:main",
         ("fig5_6_breakdown", "fold_wire")),
        ("fig7_1d_vs_2d", "bfs_1d_vs_2d:main", "fig7_1d_vs_2d"),
        ("fold_codecs", "bfs_fold_codecs:main", "fold_codecs"),
        ("table2_fig8_expansion", "bfs_expansion_variants:main",
         "table2_fig8_expansion_variants"),
        ("direction_sweep", "bfs_expansion_variants:direction_sweep",
         ("direction_sweep", "direction_levels")),
        ("exchange", "bfs_exchange:main", "exchange"),
        ("table3_realworld", "bfs_realworld:main", "table3_realworld"),
    ]
    if args.smoke:
        keep = {"algos_sweep", "fig4_strong_scaling", "fig5_6_breakdown",
                "fold_codecs", "direction_sweep", "exchange"}
        suites = [s for s in suites if s[0] in keep]
    failures = 0
    for name, entry, csv_names in suites:
        print(f"\n=== {name} ===")
        # drop the previous run's CSVs first: a failing suite must leave a
        # GAP in BENCH_bfs.json, not silently contribute stale numbers
        if isinstance(csv_names, str):
            csv_names = (csv_names,)
        for csv_name in csv_names:
            stale = os.path.join(common.OUT_DIR, f"{csv_name}.csv")
            if os.path.exists(stale):
                os.remove(stale)
        t0 = time.time()
        try:
            print(common.run_worker("suite_worker.py", *entry.split(":"),
                                    timeout=None), end="")
            print(f"--- {name} done in {time.time() - t0:.0f}s")
        except Exception:
            failures += 1
            print(f"--- {name} FAILED:\n{traceback.format_exc()[-1500:]}")
    write_bench_json()
    errors = validate_bench(args.smoke)
    for e in errors:
        print(f"VALIDATION: {e}")
    if failures or errors:
        sys.exit(1)
    print("validation OK")


if __name__ == "__main__":
    main()
