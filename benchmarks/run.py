"""One command for every cell:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It finds the cell in BENCHMARK.json, its configuration, its traffic mix and
its per-layer metrics by name, picks the driver by the mix's `kind`, and finds
everything that depends on the model's architecture by the `family` the
configuration's file names. One process holds the chip. The last line of
standard output is the result.
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--control", type=int, choices=(0, 1), default=0,
                        help="also judge the lower-precision control and the planted "
                             "faults in the program's place (by hand)")
    parser.add_argument("--rehearsal", default=None,
                        help="a JSON file of overrides: run off the chip at a "
                             "tiny size and report no metric")
    args = parser.parse_args(argv)

    import harness

    manifest = harness.load_manifest()
    cell = harness.find_cell(manifest, args.workload)
    cfg = harness.load_config(manifest, cell["config"])
    mix = harness.load_traffic(cell["traffic"])
    limits = harness.load_limits(cell["name"])
    if args.rehearsal:
        import json

        with open(args.rehearsal) as f:
            over = json.load(f)
        cfg = _merge(cfg, over.get("config", {}))
        mix = _merge(mix, over.get("traffic", {}))
        limits = _merge(limits, over.get("limits", {}))
        # stand-in peaks for the rehearsal's device, so that the readers of a
        # share of the peak run end to end off the chip too
        import peaks

        peaks.PEAKS.update(over.get("peaks", {}))
    if mix["kind"] not in harness.DRIVERS:
        raise SystemExit(f"no driver for traffic kind {mix['kind']!r}")
    driver = __import__(harness.DRIVERS[mix["kind"]])
    family = harness.load_family(cfg, needs=driver.NEEDS)
    devices = harness.require_chips(cell["chips"], bool(args.rehearsal))
    harness.enable_compile_cache()
    result = driver.run(
        manifest=manifest, cell=cell, cfg=cfg, family=family, mix=mix,
        limits=limits, args=args, devices=devices,
        t_process_start=T_PROCESS_START,
    )
    if args.rehearsal:
        # a run off the chip prints nothing under a device metric's name
        result["rehearsal_numbers"] = result.pop("metrics")
        result["metrics"] = {}
        result.pop("breakdown", None)
    harness.emit(result)
    return 0


def _merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


if __name__ == "__main__":
    sys.exit(main())
