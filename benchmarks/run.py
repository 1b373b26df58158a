"""Benchmark orchestrator — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  QUICK grids by default;
``BENCH_FULL=1`` restores the paper's full sweeps.  Select subsets with
``python -m benchmarks.run fig1 fig8 table2``.

``python -m benchmarks.run --smoke`` is the CI tier: tiny shapes
(``BENCH_SMOKE=1``), interpret-mode fused-kernel parity canaries and a
preprocessing-pipeline parity pass — fast enough for every merge, and
any bit mismatch fails the run.  Smoke mode never
writes trajectory JSON files.

OPH suites write ``BENCH_oph.json``, the preprocess suite writes
``BENCH_preprocess.json``, the streaming-trainer suite writes
``BENCH_streaming.json``, the serving suite writes
``BENCH_serving.json`` and the retrieval suite writes
``BENCH_retrieval.json`` (override paths with ``BENCH_OPH_JSON`` /
``BENCH_PREPROCESS_JSON`` / ``BENCH_STREAMING_JSON`` /
``BENCH_SERVING_JSON`` / ``BENCH_RETRIEVAL_JSON``) so the
preprocessing-, training-, serving- and retrieval-throughput
trajectories are machine-readable across commits.
"""
import json
import os
import sys
import traceback

# Suites whose records feed the perf-trajectory files.
OPH_SUITES = ("kernels_oph", "oph_curve")
PREPROCESS_SUITES = ("preprocess",)
STREAMING_SUITES = ("streaming", "multihost")
SERVING_SUITES = ("serving",)
RETRIEVAL_SUITES = ("retrieval",)

SMOKE_DEFAULT = ["kernels_fused", "preprocess", "streaming", "serving",
                 "retrieval"]


def _write_json(path_env: str, default: str, bench: str, records) -> None:
    path = os.environ.get(path_env, default)
    payload = {
        "bench": bench,
        "records": [
            {"name": name, "us_per_call": us, "derived": derived}
            for name, us, derived in records
        ],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {path} ({len(records)} records)", file=sys.stderr)


def main() -> None:
    argv = sys.argv[1:]
    smoke = "--smoke" in argv
    if smoke:
        argv = [a for a in argv if a != "--smoke"]
        os.environ["BENCH_SMOKE"] = "1"   # before benchmarks.* imports

    from benchmarks import (kernel_bench, paper_figures,
                            preprocess_bench, retrieval_bench,
                            roofline_report, serving_bench,
                            streaming_bench)

    suites = {
        "fig1": paper_figures.fig1_fig2_svm,
        "fig3": paper_figures.fig3_fig4_logistic,
        "fig5": paper_figures.fig5_fig6_vw_vs_bbit,
        "fig7": paper_figures.fig7_train_time_vw_vs_bbit,
        "fig8": paper_figures.fig8_universal_vs_permutations,
        "table2": paper_figures.table2_preprocessing_cost,
        "variance": paper_figures.variance_check,
        "compact": paper_figures.compact_index_trick,
        "oph_curve": paper_figures.oph_vs_minwise_vs_vw,
        "kernels_minhash": kernel_bench.minhash_bench,
        "kernels_oph": kernel_bench.oph_bench,
        "kernels_fused": kernel_bench.fused_encode_bench,
        "kernels_bbit": kernel_bench.bbit_linear_bench,
        "kernels_vw": kernel_bench.vw_sketch_bench,
        "roofline": roofline_report.roofline_rows,
        "preprocess": preprocess_bench.preprocess_bench,
        "streaming": streaming_bench.streaming_bench,
        "multihost": streaming_bench.multihost_bench,
        "serving": serving_bench.serving_bench,
        "retrieval": retrieval_bench.retrieval_bench,
    }
    if argv:
        selected = argv
    elif smoke:
        selected = SMOKE_DEFAULT
    else:
        selected = list(suites)
    print("name,us_per_call,derived")
    failures = 0
    trajectories = {           # suite group → (records, failed flag)
        "oph": [OPH_SUITES, [], False],
        "preprocess": [PREPROCESS_SUITES, [], False],
        "streaming": [STREAMING_SUITES, [], False],
        "serving": [SERVING_SUITES, [], False],
        "retrieval": [RETRIEVAL_SUITES, [], False],
    }
    for name in selected:
        try:
            rows = suites[name]()
            for group in trajectories.values():
                if name in group[0] and rows:
                    group[1].extend(rows)
        except Exception:  # noqa: BLE001
            failures += 1
            for group in trajectories.values():
                group[2] = group[2] or name in group[0]
            print(f"{name},0,ERROR")
            traceback.print_exc()
    if not smoke:              # tiny smoke shapes must never clobber
        if trajectories["oph"][1] and not trajectories["oph"][2]:
            _write_json("BENCH_OPH_JSON", "BENCH_oph.json", "oph",
                        trajectories["oph"][1])
        if (trajectories["preprocess"][1]
                and not trajectories["preprocess"][2]):
            _write_json("BENCH_PREPROCESS_JSON", "BENCH_preprocess.json",
                        "preprocess", trajectories["preprocess"][1])
        if (trajectories["streaming"][1]
                and not trajectories["streaming"][2]):
            _write_json("BENCH_STREAMING_JSON", "BENCH_streaming.json",
                        "streaming", trajectories["streaming"][1])
        if (trajectories["serving"][1]
                and not trajectories["serving"][2]):
            _write_json("BENCH_SERVING_JSON", "BENCH_serving.json",
                        "serving", trajectories["serving"][1])
        if (trajectories["retrieval"][1]
                and not trajectories["retrieval"][2]):
            _write_json("BENCH_RETRIEVAL_JSON", "BENCH_retrieval.json",
                        "retrieval", trajectories["retrieval"][1])
    for key, (group_suites, records, failed) in trajectories.items():
        if failed:
            # never clobber a complete trajectory file with partials
            print(f"# BENCH_{key}.json not written (a suite failed)",
                  file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark suites failed")


if __name__ == "__main__":
    main()
