"""Run a list of cells one after another, each in a process of its own (this
parent never touches JAX), and keep every result line. By hand, on the chip:

    chiprun -- python benchmarks/tools/batch.py <label> '<json list of runs>'

A run is {"workload", "seed", "seconds", "trace", and optionally "control",
"env", "cwd"}. Results go to chiprun_out/<label>.jsonl, the end of
each run's standard error to chiprun_out/<label>.err.
"""
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    label, runs = sys.argv[1], json.loads(sys.argv[2])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for run in runs:
        cwd = os.path.join(ROOT, run.get("cwd", "."))
        cmd = [sys.executable, "benchmarks/run.py", "--workload", run["workload"],
               "--seed", str(run["seed"]), "--seconds", str(run["seconds"]),
               "--trace", str(run.get("trace", 0))]
        if "control" in run:
            cmd += ["--control", str(run["control"])]
        env = dict(os.environ, **run.get("env", {}))
        t = time.time()
        proc = subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True)
        wall = time.time() - t
        last = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
        try:
            line = json.loads(last)
        except ValueError:
            line = {"unparsed": last[-2000:]}
        record = {"run": run, "rc": proc.returncode, "wall_s": round(wall, 1),
                  "result": line}
        with open(os.path.join(out_dir, label + ".jsonl"), "a") as f:
            f.write(json.dumps(record) + "\n")
        with open(os.path.join(out_dir, label + ".err"), "a") as f:
            f.write(f"=== {json.dumps(run)} rc={proc.returncode} wall={wall:.1f}\n")
            f.write(proc.stderr[-6000:] + "\n")
        short = {k: v for k, v in line.items() if k in (
            "correct", "attempted", "failed", "metrics", "window", "stand_ins", "checks")}
        print(json.dumps({"run": run, "rc": proc.returncode, "wall_s": round(wall, 1), **short}), flush=True)
        if proc.returncode:
            print(proc.stderr[-3000:], flush=True)


if __name__ == "__main__":
    main()
