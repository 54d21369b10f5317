#!/usr/bin/env bash
# Refresh every bench_results/ artifact, serially, on a machine with the
# chip (one process at a time holds it). Usage:
#
#   tools/refresh_artifacts.sh
#
# Each bench prints one JSON line on stdout; stderr is captured beside
# the artifact. A bench that finds no TPU exits 3 with a null line, and a
# failed bench leaves the previous artifact in place.
set -u
cd "$(dirname "$0")/.."
platform=tpu

run() { # run <artifact-stem> <cmd...>
  local stem="$1"; shift
  echo "== $stem: $*" >&2
  local out rc
  # no pipe here: a pipe would mask the bench's exit code with tail's,
  # and a bench that exits 3 with a {"value": null} diagnostics line
  # (bench_common.exit_null) must NOT overwrite the previous artifact.
  # stderr goes to a temp first for the same reason: the kept .json and
  # its committed .stderr provenance must stay a matched pair
  out=$("$@" 2>"bench_results/${stem}.stderr.tmp"); rc=$?
  out=$(printf '%s\n' "$out" | tail -n 1)
  if [ "$rc" -eq 0 ] && [ -n "$out" ]; then
    # keep the artifact this run replaces so bench_diff can report the
    # round-over-round movement below
    if [ -f "bench_results/${stem}.json" ]; then
      cp -f "bench_results/${stem}.json" "bench_results/${stem}.prev.tmp"
    fi
    printf '%s\n' "$out" > "bench_results/${stem}.json"
    mv -f "bench_results/${stem}.stderr.tmp" "bench_results/${stem}.stderr"
    rm -f "bench_results/${stem}.failed.json" "bench_results/${stem}.failed.stderr"
    echo "   -> $out" >&2
    # advisory diff against the previous round's artifact: a slow machine
    # is not a broken bench, so the verdict never fails the refresh
    if [ -f "bench_results/${stem}.prev.tmp" ]; then
      python tools/bench_diff.py "bench_results/${stem}.prev.tmp" \
        "bench_results/${stem}.json" >&2 || true
      rm -f "bench_results/${stem}.prev.tmp"
    fi
  else
    mv -f "bench_results/${stem}.stderr.tmp" "bench_results/${stem}.failed.stderr"
    # a failed bench may still have printed the {"value": null}
    # diagnostics line (bench_common.exit_null) — keep it beside the
    # intact artifact. Remove
    # any previous failure's copy first: the failed.json/.failed.stderr
    # pair must come from the SAME run
    rm -f "bench_results/${stem}.failed.json"
    if [ -n "$out" ]; then
      printf '%s\n' "$out" > "bench_results/${stem}.failed.json"
    fi
    echo "   FAILED rc=$rc (artifact kept); see bench_results/${stem}.failed.*" >&2
  fi
}

run "config2_${platform}"          python bench.py
run "config2_hostcol_${platform}"  python bench.py --host-col
# repeat-heavy cache-on/cache-off pair (BENCH_r10 headline shape): the
# routing-tier aggregate the vectorized host path is meant to raise
run "config2_rr90_lc64_${platform}" python bench.py --repeat-ratio 0.9 --line-cache-mb 64
run "config2_rr90_${platform}"      python bench.py --repeat-ratio 0.9
# host-phase profile (tools/profile_host.py): ingest/key/extract/
# assemble/finalize in isolation, scalar vs vectorized lanes — the
# PERF.md §14 phase table is read from these artifacts
run "profile_host_${platform}"      python tools/profile_host.py
run "profile_host_rr90_${platform}" python tools/profile_host.py --repeat-ratio 0.9
run "config3_1m_singlechip_${platform}" python bench.py --lines 1000000
# the full sharded DP program at corpus scale on the virtual 8-device
# mesh. Runs on EVERY refresh round (bench_mesh.py pins itself to the
# virtual CPU mesh, hence the cpu stem) so
# the artifact never goes stale beside freshly-stamped siblings; real
# multi-chip mode is LOG_PARSER_TPU_MESH=real on a multi-chip host
run "config3_1m_mesh8_cpu" python bench_mesh.py --devices 8 --lines 1000000
# measured shard-program overhead (VERDICT r4 #4): the FULL ShardedEngine
# vs the plain engine at matched batch. On a TPU host the mesh=1 real row
# isolates program structure (halos/all_gather/concat, zero real
# communication) — the factor under the config-3 "per-chip x N" projection
LOG_PARSER_TPU_MESH=real run "config3_shard_overhead_mesh1_tpu" \
  python bench_mesh.py --devices 1 --lines 200000 --overhead
run "config3_shard_overhead_mesh8_cpu" \
  python bench_mesh.py --devices 8 --lines 200000 --overhead
# the Pallas kernel verdicts (PERF.md §9 + §12): session-matched A/B of
# BOTH kernel tiers (bitglush, union multi-DFA) against their XLA scan
# baselines; the bitglush kernel gets deleted if its pallas_over_xla
# comes back >= ~1 (VERDICT r4 #6)
run "kernels_ab_tpu" python tools/probe_kernels.py
run "config4_2k_${platform}"       python bench_bank.py --patterns 2000 --lines 65536
run "config4_10k_${platform}"      python bench_bank.py --patterns 10000 --lines 65536
run "config5_direct_${platform}"   python bench_latency.py
run "config5_http_${platform}"     python bench_latency.py --http
run "config5_http_c4_${platform}"  python bench_latency.py --http --concurrency 4
# follow-mode TTFD vs blob-mode end-to-end on the repeat-heavy corpus
# (ISSUE 9 acceptance shape; headline row of BENCH_r09)
run "config5_stream_${platform}" \
  python bench_latency.py --stream --repeat-ratio 0.9 --line-cache-mb 64
# fleet front-door: 1,000 tenants, zipf traffic, 3 backends behind the
# router, one hot tenant moved live by the placement loop, plus the
# compiled-pack dedupe savings. Pure subprocess HTTP — fixed cpu stem
run "fleet_1k_cpu" python bench_mesh.py --fleet
