"""Compile the timed programs at full size for a described v5e, with no chip.

Run by hand (`JAX_PLATFORMS=cpu python benchmarks/tools/aot_compile.py serve
galactica-1.3b 64`): prints what `memory_analysis()` reports, which the
configuration files record. Nothing here is a timing.
"""
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "benchmarks"), ROOT]

import jax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def _patch_kernels():
    import importlib

    for name in ("flash_attention", "flash_decode"):
        module = importlib.import_module("distributeddeeplearning_tpu.ops." + name)
        module._use_interpret = lambda: False


def _mem(compiled):
    m = compiled.memory_analysis()
    return {
        k: getattr(m, k)
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "alias_size_in_bytes", "temp_size_in_bytes",
                  "generated_code_size_in_bytes")
    }


def _family(cfg):
    import harness

    return harness.load_family(cfg)


def serve(config_name, kv_pages):
    cfg = _config(config_name)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    programs, pool_bytes = _family(cfg).aot_serve_programs(cfg, kv_pages, one)
    out = {"kv_pages": kv_pages, "pool_logical_bytes": pool_bytes}
    for name, (fn, shapes) in programs.items():
        t = time.time()
        out[name] = _mem(jax.jit(fn, donate_argnums=(1,)).lower(*shapes).compile())
        out[name + "_compile_s"] = round(time.time() - t, 1)
    print(json.dumps(out))


def train(config_name, job_name, chips=1):
    cfg = _config(config_name)
    family = _family(cfg)
    import train_driver

    with open(os.path.join(ROOT, "benchmarks", "traffic", job_name + ".json")) as f:
        job = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = list(topo.devices)[:chips]
    t = time.time()
    compiled = train_driver.aot_compile(cfg, family, job, devices)
    out = _mem(compiled)
    out["compile_s"] = round(time.time() - t, 1)
    text = compiled.as_text()
    out["collectives"] = {k: text.count(k + "(") + text.count(k + "-start(")
                          for k in ("all-gather", "reduce-scatter", "all-reduce")}
    out["tpu_custom_calls"] = text.count("tpu_custom_call")
    print(json.dumps(out))


if __name__ == "__main__":
    _patch_kernels()
    if sys.argv[1] == "serve":
        serve(sys.argv[2], int(sys.argv[3]))
    else:
        train(sys.argv[2], sys.argv[3], int(sys.argv[4]) if len(sys.argv) > 4 else 1)
