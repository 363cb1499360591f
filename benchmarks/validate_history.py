"""Bench-trajectory gate: a fresh BENCH_*.json vs the committed baseline.

The bench artifacts are trajectory data -- wall-clock numbers move with
the host and are NEVER gated here.  What must not regress across PRs is
the correctness surface:

  * the schema may only move FORWARD ("BENCH_bfs/v8" -> v9 is fine, -> v7
    is a regression);
  * every agreement flag that was true in the baseline stays true
    (codecs_agree / direction_agree / exchange_agree, BENCH_algos
    per-algo codecs_agree) -- a suite that silently stopped running
    reads as null and FAILS the gate;
  * every fold codec / algo / exchange strategy covered by the baseline
    is still covered;
  * when the fresh run used the same graph scale and grid as the
    baseline, the deterministic correctness counters must match EXACTLY:
    the fold-codec lvl_sum/pred_sum checksums (the generator is seeded,
    the engine is bit-reproducible) and the per-strategy exchange message
    totals (pure functions of C and the level count).

CI stashes the committed bench_out/BENCH_*.json before the fresh smoke
run overwrites them, then calls:

    python benchmarks/validate_history.py --baseline <stash> [--fresh bench_out]

Exit 0 = trajectory OK; non-zero prints one line per violation.
"""
import argparse
import json
import os
import sys

AGREE_FLAGS = ("codecs_agree", "direction_agree", "exchange_agree")


def _load(d, name, errors):
    p = os.path.join(d, f"{name}.json")
    if not os.path.exists(p):
        errors.append(f"{p} missing")
        return None
    try:
        with open(p) as f:
            return json.load(f)
    except json.JSONDecodeError as e:
        errors.append(f"{p}: invalid JSON ({e})")
        return None


def _schema_version(doc, prefix, errors, who):
    s = (doc or {}).get("schema") or ""
    if not s.startswith(prefix + "/v"):
        errors.append(f"{who}: schema {s!r} does not match {prefix}/vN")
        return None
    try:
        return int(s.split("/v", 1)[1])
    except ValueError:
        errors.append(f"{who}: unparseable schema version {s!r}")
        return None


def compare_bfs(base, fresh) -> list:
    errors = []
    bv = _schema_version(base, "BENCH_bfs", errors, "baseline")
    fv = _schema_version(fresh, "BENCH_bfs", errors, "fresh")
    if bv is not None and fv is not None and fv < bv:
        errors.append(f"BENCH_bfs schema went BACKWARD: v{fv} < baseline "
                      f"v{bv}")
    for flag in AGREE_FLAGS:
        if base.get(flag) is True and fresh.get(flag) is not True:
            errors.append(f"BENCH_bfs.{flag} regressed: baseline true, "
                          f"fresh {fresh.get(flag)!r} (a suite that "
                          f"stopped running reads as null and fails)")
    b_codecs, f_codecs = base.get("fold_codecs") or {}, \
        fresh.get("fold_codecs") or {}
    for codec, bc in b_codecs.items():
        fc = f_codecs.get(codec)
        if fc is None:
            errors.append(f"BENCH_bfs.fold_codecs lost codec {codec!r}")
            continue
        # deterministic checksums: seeded generator + bit-reproducible
        # engine => same scale + grid must reproduce the same outputs
        if (bc.get("scale"), bc.get("grid")) == (fc.get("scale"),
                                                 fc.get("grid")):
            for k in ("lvl_sum", "pred_sum"):
                if bc.get(k) != fc.get(k):
                    errors.append(
                        f"BENCH_bfs.fold_codecs[{codec}].{k} changed at "
                        f"unchanged scale/grid: {bc.get(k)} -> {fc.get(k)}")
    b_ex = {(a.get("strategy"), a.get("codec")): a
            for a in base.get("exchange") or []}
    f_ex = {(a.get("strategy"), a.get("codec")): a
            for a in fresh.get("exchange") or []}
    for key, ba in b_ex.items():
        fa = f_ex.get(key)
        if fa is None:
            errors.append(f"BENCH_bfs.exchange lost entry {key}")
            continue
        if (ba.get("scale"), ba.get("C")) == (fa.get("scale"),
                                              fa.get("C")):
            for k in ("levels", "total_msgs"):
                if ba.get(k) != fa.get(k):
                    errors.append(
                        f"BENCH_bfs.exchange[{key}].{k} changed at "
                        f"unchanged scale/C: {ba.get(k)} -> {fa.get(k)}")
    return errors


def compare_algos(base, fresh) -> list:
    errors = []
    bv = _schema_version(base, "BENCH_algos", errors, "baseline")
    fv = _schema_version(fresh, "BENCH_algos", errors, "fresh")
    if bv is not None and fv is not None and fv < bv:
        errors.append(f"BENCH_algos schema went BACKWARD: v{fv} < "
                      f"baseline v{bv}")
    b_algos, f_algos = base.get("algos") or {}, fresh.get("algos") or {}
    for name, ba in b_algos.items():
        fa = f_algos.get(name)
        if fa is None:
            errors.append(f"BENCH_algos lost algo {name!r}")
            continue
        if ba.get("codecs_agree") is True and fa.get("codecs_agree") \
                is not True:
            errors.append(f"BENCH_algos[{name}].codecs_agree regressed: "
                          f"baseline true, fresh "
                          f"{fa.get('codecs_agree')!r}")
    return errors


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--baseline", required=True,
                    help="directory holding the committed BENCH_*.json")
    ap.add_argument("--fresh", default="bench_out",
                    help="directory holding the just-produced BENCH_*.json")
    args = ap.parse_args(argv)

    errors = []
    base_bfs = _load(args.baseline, "BENCH_bfs", errors)
    fresh_bfs = _load(args.fresh, "BENCH_bfs", errors)
    if base_bfs is not None and fresh_bfs is not None:
        errors += compare_bfs(base_bfs, fresh_bfs)
    base_algos = _load(args.baseline, "BENCH_algos", errors)
    fresh_algos = _load(args.fresh, "BENCH_algos", errors)
    if base_algos is not None and fresh_algos is not None:
        errors += compare_algos(base_algos, fresh_algos)

    for e in errors:
        print(f"HISTORY: {e}")
    if errors:
        sys.exit(1)
    print("bench trajectory OK")


if __name__ == "__main__":
    main()
