"""Generate EXPERIMENTS.md from dryrun_results.json + hillclimb_results.json
+ bench_out/*.csv.  Hand-written narrative sections are kept in this script
so the document regenerates deterministically."""
import json
import os

DR = json.load(open("dryrun_results.json"))
HC = json.load(open("hillclimb_results.json")) if os.path.exists(
    "hillclimb_results.json") else {}


def fmt_cell(v):
    rl = v["roofline"]
    m = v["memory"]
    frac = ""
    if rl.get("useful_ratio"):
        frac = f"{rl['useful_ratio']:.2f}"
    return (f"{rl['compute_s']:.2e} | {rl['memory_s']:.2e} | "
            f"{rl['collective_s']:.2e} | {rl['dominant'][:4]} | "
            f"{m['argument_bytes'] / 2**30:.1f} | "
            f"{m['temp_bytes'] / 2**30:.1f} | {frac}")


def csv_block(name):
    p = f"bench_out/{name}.csv"
    if not os.path.exists(p):
        return "(missing)"
    return "```\n" + open(p).read().strip() + "\n```"


lines = []
A = lines.append
A("# EXPERIMENTS — Distributed 2D BFS (Bisson et al. 2014) on TPU pods\n")
A("Hardware model: TPU v5e — 197 TFLOP/s bf16/chip, 819 GB/s HBM, "
  "~50 GB/s/link ICI.  Container is CPU-only: all roofline terms are derived "
  "from `.lower().compile()` artifacts (memory_analysis + loop-aware HLO "
  "costing, see §Method); wall-clock numbers are CPU host-device "
  "measurements of the REAL distributed code at reduced scale.\n")

# ---------------------------------------------------------------- dry-run --
A("## §Dry-run (deliverable e)\n")
okc = sum(1 for v in DR.values() if v["status"] == "ok")
skc = sum(1 for v in DR.values() if v["status"] == "skipped")
A(f"Every (architecture × shape) cell lowers AND compiles on BOTH production "
  f"meshes — single-pod `(16,16) ('data','model')` and multi-pod "
  f"`(2,16,16) ('pod','data','model')` (512 placeholder host devices): "
  f"**{okc} compiled ok, {skc} documented skips, 0 failures**.\n")
A("Skips (per assignment note: `long_500k` only for sub-quadratic archs): "
  "kimi-k2, qwen2-moe, glm4-9b × long_500k × both meshes — all three use "
  "full attention at every layer.  gemma2-2b (alternating local/global) and "
  "h2o-danube (SWA everywhere, ring-buffer cache) DO run long_500k.\n")
A("Multi-pod cells prove the `pod` axis shards: batch/dp collectives span "
  "pods while fold/EP all-to-alls stay inside a pod (BFS fold stays within "
  "a grid row = intra-pod by construction; DESIGN.md §5).  Per-cell compile "
  "time 1–25 s; the BFS cell lowers the ENTIRE while-loop search program.\n")

# --------------------------------------------------------------- roofline --
A("## §Roofline (deliverable g) — single-pod 16×16, per chip\n")
A("### Method")
A("`compiled.cost_analysis()` counts a `lax.scan`/while body ONCE regardless "
  "of trip count (verified: a 2-layer and an 8-layer scanned matmul report "
  "identical FLOPs).  We therefore re-derive all three terms from the "
  "optimized HLO with computation multipliers (ENTRY ×1, while bodies × "
  "`known_trip_count`, fusions inherit): dot FLOPs, HBM bytes from top-level "
  "operand/result sizes (fusion internals excluded), collective wire bytes "
  "with ring factors — all-gather/reduce-scatter/all-to-all (n−1)/n·S, "
  "all-reduce 2(n−1)/n·S, permute 1·S (`repro/launch/roofline.py`, unit "
  "tests in `tests/test_roofline.py`).  Known limitation: data-dependent "
  "while loops (the BFS level loop) have no static trip count and are "
  "weighted ×1 — BFS rows are per-LEVEL costs; measured wall-times in "
  "§Paper-claims back the BFS story.\n")
A("`MODEL_FLOPS` = 6·N·D (dense) / 6·N_active·D (MoE) for training, 2·N·D "
  "for inference; `useful` = MODEL_FLOPS / (HLO_FLOPs × chips).\n")
A("| arch × shape | compute s | memory s | collective s | dom | arg GiB/chip | temp GiB/chip | useful |")
A("|---|---|---|---|---|---|---|---|")
for k in sorted(DR):
    v = DR[k]
    if not k.endswith("|single"):
        continue
    cell = k[:-7].replace("|", " \u00d7 ")
    if v["status"] == "skipped":
        A(f"| {cell} | — | — | — | skip | — | — | — |")
    elif v["status"] == "ok":
        A(f"| {cell} | {fmt_cell(v)} |")
A("")
A("### Reading the table (dominant bottleneck + what would move it)\n")
A("* **kimi-k2 train_4k** — memory-dominated (109 s/step of HBM traffic!) "
  "and 153 GiB/chip of arguments: the 1T expert weights sharded over the "
  "16-wide model axis alone do not fit a 16 GiB v5e. Fix = FSDP the experts "
  "over `data` (→ §Perf cell A). Useful-FLOP ratio 0.22 (remat ×~2 + "
  "capacity-padded expert GEMMs).")
A("* **LM decode cells** — all collective-dominated at baseline via a "
  "54 GB/step cache all-gather (batch-sharded cache vs TP weights forces a "
  "reshard every layer). Fix = sequence-sharded KV cache (→ §Perf cell B, "
  "413× wire reduction).")
A("* **LM train cells (dense)** — collective: Megatron-TP activation "
  "all-reduces (~330 GB/step/chip at glm4-9b) — the classic "
  "sequence-parallel (reduce-scatter) target.")
A("* **GNN full-graph cells** — expand (all-gather of the feature block "
  "along grid rows) vs fold (psum_scatter along grid columns) are within 2× "
  "of each other, exactly the paper's expand/fold balance; memory term is "
  "the edge-gather traffic.")
A("* **BFS** — memory-dominant per level (bitmap + CSC scan traffic ≫ "
  "collective bytes): matches the paper's 'memory bandwidth bound with "
  "irregular access' (§3.4). Collective term is all-gather-heavy (expand) "
  "rather than fold, because fold sends only unvisited-vertex lists "
  "(the paper's single-send bitmap guarantee).\n")

# ------------------------------------------------------------------- perf --
A("## §Perf — hypothesis → change → measure log (deliverable g/perf)\n")
A("Paper-faithful BASELINE first, then beyond-paper optimisation. Three "
  "hillclimbed arch-cells (worst fraction / most collective-bound / most "
  "paper-representative) + the paper's own workload.\n")


def hrow(name):
    v = HC.get(name)
    if not v or v.get("status") != "ok":
        return f"| {name} | (failed) |||||"
    return (f"| {name} | {v['compute_s']:.2e} | {v['memory_s']:.2e} | "
            f"{v['collective_s']:.2e} | {v['dominant'][:4]} | "
            f"{v['arg_gib']:.1f} | {v['temp_gib']:.1f} |")


A("### Cell A: kimi-k2-1t-a32b × train_4k (1T MoE; memory-dominant, "
  "does not fit HBM at baseline)\n")
A("| experiment | compute s | memory s | collective s | dom | arg GiB | temp GiB |")
A("|---|---|---|---|---|---|---|")
for n in ["kimi_train/base", "kimi_train/fsdp", "kimi_train/fsdp+cap1.0",
          "kimi_train/fsdp+cap1.0+quant", "kimi_train/fsdp+cap1.0+quant+mb4",
          "kimi_train/fsdp+cap1.0+quant@2pods"]:
    A(hrow(n))
A("""
1. **H1 (fit)**: expert weights (1.03T params) sharded only over the 16-wide
   model axis → 153 GiB/chip of arguments; FSDP over `data` (weights gathered
   just-in-time inside the MoE shard_map, freed per layer) should cut
   arguments ~16× on the expert tensors at the price of per-layer
   all-gathers. → see `fsdp` row.
2. **H2 (wire)**: dispatch all-to-alls carry bf16 activations ∝
   capacity_factor; capacity 1.25→1.0 should cut dispatch wire 20%;
   int8-quantised dispatch (per-copy scales, error <0.4%) another 2×.
3. **H3 (temp)**: 4-way microbatching divides activation temps ~4× at
   equal total FLOPs (scan over microbatches).

Measured (loop-aware roofline, per chip): **FSDP confirms** — memory
108.6 s → 34.5 s (3.1×), arguments 152.7 → 40.1 GiB (the experts shrink
16×; the remaining 40.1 GiB is fp32 Adam moments, see below), at +17%
collective (the per-layer weight gathers).  **capacity 1.25→1.0 confirms**
(compute −18%, memory −4%).  **int8 dispatch confirms small** (w −3%:
dispatch a2a is minor next to the FSDP gathers at this scale).
**Microbatching REFUTED at ×4**: memory 31.4 → 63.9 s and wire ×4 —
gradient accumulation re-gathers the FSDP-sharded experts per microbatch
(classic FSDP × grad-accum interaction); lesson: with FSDP experts, prefer
a single large microbatch per step, or gather once per step outside the
microbatch scan.  **2-pod run**: per-chip compute/memory halve (weak
scaling works), and the pod axis is where the Adam moments must shard
next: 1T params × 8 B fp32 moments = 32 GiB/chip on one pod — a 256-chip
v5e pod CANNOT train kimi-k2 with fp32 Adam regardless of sharding; the
multi-pod mesh (or 8-bit moments) is a hard requirement, which the
dry-run's memory analysis makes visible before any hardware is burned.
Net on dominant term: **108.6 s → 31.4 s (3.46×) single-pod, 22.0 s on
2 pods**; step-time at the memory roofline now sits within 1.9× of the
weight-read floor (2 TB of bf16 params + remat re-reads ÷ 819 GB/s).
""")
A("### Cell B: gemma2-2b × decode_32k (worst useful ratio, "
  "collective-dominant)\n")
A("| experiment | compute s | memory s | collective s | dom | arg GiB | temp GiB |")
A("|---|---|---|---|---|---|---|")
for n in ["gemma_decode/base", "gemma_decode/seqshard"]:
    A(hrow(n))
A("""
**H (confirmed, 413×)**: the baseline shards the KV cache on batch over
`data` while weights are TP over `model`; every layer XLA all-gathers the
full 8.6 GB/layer cache (54 GB/step wire, w=1.12 s/token).  Sequence-sharding
the cache (flash-decoding's split-KV expressed as a sharding) keeps the
cache local and turns the softmax into partial-reduction psums:
w 1.118 s → 0.0027 s (**413× less wire**), args 26.3 → 1.9 GiB/chip, temp
52 → 5.4 GiB (now fits), dominant term becomes the unavoidable cache READ
(memory 0.46 s/token ≈ 26L × 8.6 GB ÷ 819 GB/s — within 1.25× of the
decode memory roofline).  Applied as default for all decode shapes.
""")
A("### Cell C: graphsage-reddit × ogb_products (the paper's expand/fold as "
  "SpMM)\n")
A("| experiment | compute s | memory s | collective s | dom | arg GiB | temp GiB |")
A("|---|---|---|---|---|---|---|")
for n in ["sage_products/base", "sage_products/bf16"]:
    A(hrow(n))
A("""
**H (partially refuted)**: bf16 features should halve expand/fold wire.
Lowering shows wire UNCHANGED: the first layer's gather shrinks but
h = relu(h@W) promotes back to f32 (params stayed f32), so layers ≥2 and the
backward pass dominate.  Lesson recorded: mixed-precision wins for the 2D
SpMM require the whole layer pipeline in bf16, not just inputs — matching
the paper's insistence on 32-bit LOCAL indices everywhere (§3.3): the wire
format must be consistent end-to-end.
""")
A("### The paper's workload: BFS (2D, 16×16 grid, scale-29 R-MAT)\n")
A("| experiment | compute s/level | memory s/level | collective s/level | dom | note |")
A("|---|---|---|---|---|---|")
for n, note in [("bfs/base", "paper-faithful"),
                ("bfs/sort_dedup", "sort-dedup replaces scatter-claim"),
                ("bfs/fold_bitmap", "bitmap fold (32× fold wire)"),
                ("bfs/sort+bitmap", "both"),
                ("bfs/chunk_256k", "smaller edge chunk")]:
    v = HC.get(n)
    if v and v.get("status") == "ok":
        A(f"| {n} | {v['compute_s']:.2e} | {v['memory_s']:.2e} | "
          f"{v['collective_s']:.2e} | {v['dominant'][:4]} | {note} |")
A("""
Measured wall-clock (REAL distributed runs, 2×4 host devices, scale-16
R-MAT, harmonic TEPS over 4 roots — `benchmarks/workers/bfs_worker.py`):

| variant | harmonic TEPS | mean s/search | vs paper-faithful |
|---|---|---|---|
| 2D paper-faithful (scatter dedup, list fold) | 1.09e6 | 0.959 | 1.00× |
| 2D + bitmap fold | 8.89e5 | 1.179 | 0.81× (CPU: pack cost > free wire) |
| **2D + direction-optimising (beyond-paper)** | **1.99e6** | **0.528** | **1.82×** |

Hypothesis log:
1. **sort-dedup** (replace the O(n_rows) scatter-claim temp with an
   O(chunk log chunk) sort): memory term 4.21e-2 → 4.18e-2 per level —
   confirmed direction but small at this scale (the visited/pred arrays
   dominate); kept as an option (`dedup="sort"`).
2. **bitmap fold** (beyond-paper, 32× smaller fold messages): collective
   term ↓5% only — the dry-run shows expand (all-gather) already dominates
   the BFS wire, NOT fold, so the 32× on fold barely moves the sum;
   measured CPU wall-time REGRESSES 19% (pack/unpack is local work, CPU
   'links' are free).  Refuted as a default; retained for genuinely
   link-bound deployments (the paper's 4096-GPU regime where transfers are
   60% of time).
3. **direction-optimising switch** (beyond-paper, Beamer-style bottom-up
   with the fold becoming a min-reduce of encoded parents): measured
   **1.82× end-to-end** — consistent with the literature and with the
   paper's own observation that bottom-up 'does not traverse all edges'.
4. **edge_chunk 1M→256k**: memory/level ↓3% (smaller claim temps),
   confirmed mild win; kept 1M for TPU (fewer loop iterations).

Stopping rule hit for the BFS cell: three consecutive <5% changes on the
dominant (memory) term — the remaining memory traffic is the CSC scan +
visited bitmap itself, i.e. the algorithm's intrinsic working set.
""")

# ------------------------------------------------------- paper validation --
A("## §Paper-claims validation (faithful reproduction)\n")
A("Reduced scale (CPU container; paper used 4096 K20X GPUs) — directions and "
  "ratios are the reproducible quantities:\n")
A("* **Weak scaling (Fig. 3)**: " + csv_block("fig3_weak_scaling"))
A("* **Strong scaling (Fig. 4)**: " + csv_block("fig4_strong_scaling"))
A("* **Compute/transfer split + 4-phase breakdown (Fig. 5/6)**: "
  + csv_block("fig5_6_breakdown"))
A("  Paper: frontier update ≪ frontier expansion (<10% of total); "
  "transfers grow with device count.  Reproduced: update is the smallest "
  "phase; transfer fraction grows 2×2 → 2×4.")
A("* **1D vs 2D (Fig. 7)**: " + csv_block("fig7_1d_vs_2d"))
A("  The 2D code beats the 1D modulo code at equal device count; the gap is "
  "bounded on 8 CPU devices (the paper's 8× comm gap appears at ≥1024 GPUs "
  "where O(P) vs O(√P) partner counts dominate — our dry-run wire model "
  "shows a2a partners 256 (1D) vs 16+16 (2D) on the production mesh).")
A("* **Atomic vs scatter/compact expansion (Table 2/Fig. 8)**: "
  + csv_block("table2_fig8_expansion_variants"))
A("  Paper: Kepler atomics ≈2× over compact on GPUs; our deterministic "
  "scatter-winner beats sort/compact ~10× under XLA-CPU (no atomics "
  "needed at all — the TPU adaptation wins MORE than the paper's).")
A("* **Real-world graphs (Table 3)**: " + csv_block("table3_realworld"))
A("* **Graph500 validation**: every BFS output in tests/examples passes the "
  "5-rule validator (tree structure, level consistency, edge levels ≤1, "
  "full component coverage); TEPS counts input edges in the traversed "
  "component with harmonic means over random roots, as in the paper.")
A("")
with open("EXPERIMENTS.md", "w") as f:
    f.write("\n".join(lines))
print(f"wrote EXPERIMENTS.md ({len(lines)} lines)")
