"""Run one benchmark cell once on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``. The state is
made on the device from ``--seed``; set-up plans, compiles and warms
every program the window runs; the window then drives the cell's traffic
for ``--seconds``. With ``--trace 0`` the last line of standard output
is the result with the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the profiler and the result carries the per-layer
metrics, ``device.busy_s`` / ``window_s`` and a ``breakdown``. Either
way the output of the window is compared with the plain reference
(``bench/reference.py``) and each number compared is printed beside its
limit, last on standard error and under ``checks`` in the result.

A line before the result gives the set-up (N, m_c, grid) and the
window's counts, compilations and replans inside it among them.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2. The compilation cache is ``JAX_COMPILATION_CACHE_DIR``
where that is set, else ``.jax_cache/`` at the root of the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    spec = harness.manifest(ROOT / "BENCHMARK.json")
    chips = harness.cell(spec, args.workload)["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing runs elsewhere",
              file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"run.py: {args.workload} needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    result = harness.measure(spec, args.workload, seed=args.seed,
                             seconds=args.seconds, trace=bool(args.trace),
                             t_start=T_START,
                             log=lambda s: print(s, flush=True))
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
