"""Compile the timed programs at full size for a described v5e, with no chip.

Run by hand (`JAX_PLATFORMS=cpu python benchmarks/tools/aot_compile.py serve
galactica-1.3b 64`): prints what `memory_analysis()` reports, which the
configuration files record. Nothing here is a timing.
"""
import json
import math
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
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


def serve(config_name, kv_pages):
    from distributeddeeplearning_tpu.models.pipelined_transformer import (
        forward_decode_paged, forward_prefill_chunk)

    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    import weights

    cfg = _config(config_name)
    geo = cfg["serving"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    params = weights.param_shapes(cfg, one)
    h = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // h
    ps, slots = geo["page_size"], geo["batch_slots"]
    nb = -(-geo["max_seq"] // ps)
    pool = (kv_pages + 1, cfg["num_hidden_layers"], ps, h, hd)
    cache = {k: jax.ShapeDtypeStruct(pool, jnp.float32, sharding=one) for k in "kv"}
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)  # noqa: E731

    def decode(p, c, tok, pos, tables):
        logits, c = forward_decode_paged(p, tok, c, pos, tables, num_heads=h,
                                         page_size=ps, kernel="pallas")
        return jnp.argmax(logits, -1), jnp.isfinite(logits).all(-1), c

    def chunk(p, c, toks, table, off):
        return forward_prefill_chunk(p, toks, c, table, off, num_heads=h,
                                     page_size=ps, kernel="pallas")

    out = {"kv_pages": kv_pages, "pool_logical_bytes": 2 * 4 * math.prod(pool)}
    t = time.time()
    out["decode"] = _mem(jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, i32(slots), i32(slots), i32(slots, nb)).compile())
    out["decode_compile_s"] = round(time.time() - t, 1)
    t = time.time()
    out["prefill_chunk"] = _mem(jax.jit(chunk, donate_argnums=(1,)).lower(
        params, cache, i32(1, geo["prefill_chunk"]), i32(nb), i32()).compile())
    out["chunk_compile_s"] = round(time.time() - t, 1)
    print(json.dumps(out))


def train(config_name, job_name, chips=1):
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    import train_driver

    cfg = _config(config_name)
    with open(os.path.join(ROOT, "benchmarks", "traffic", job_name + ".json")) as f:
        job = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = list(topo.devices)[:chips]
    t = time.time()
    compiled = train_driver.aot_compile(cfg, job, devices)
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
