"""The seam between the shared harness and a model's family.

The shared files name nothing of a family (parsed, file by file); `opt` gives
every name of the contract (`families/opt.py`'s docstring) and hands out,
unchanged, what `weights.py`, `reference.py` and `flops.py` hold; and a cell
whose configuration names no family, a family with no file, or a family
lacking a name its driver needs, stops before any device is touched."""
import ast
import glob
import json
import os

import numpy as np
import pytest
from conftest import BENCH, HERE

import flops
import harness
import reference
import run as run_module
import serve_driver
import train_driver
import weights

#: a kernel's roofline reader stays bound to its kernel's shape function
KERNEL_READER = "_roofline.py"
#: where the family's mathematics and counts live, and the seam itself
FAMILY_SIDE = {"weights.py", "reference.py", "flops.py"}
SHARED = sorted(
    [p for p in glob.glob(os.path.join(BENCH, "*.py"))
     if os.path.basename(p) not in FAMILY_SIDE]
    + glob.glob(os.path.join(BENCH, "tools", "*.py"))
    + [p for p in glob.glob(os.path.join(BENCH, "layer_metrics", "*.py"))
       if not p.endswith(KERNEL_READER)])
#: what of `reference.py` and `flops.py` every family shares
SHARED_REFERENCE = {"adamw_step", "global_norm"}
MODEL_COUNTS = {"matmul_params", "serve_token_flops", "train_token_flops"}


def _functions(module):
    with open(module.__file__) as f:
        tree = ast.parse(f.read())
    return {n.name for n in tree.body if isinstance(n, ast.FunctionDef)}


def _tiny():
    with open(os.path.join(HERE, "rehearsal_tiny.json")) as f:
        over = json.load(f)
    manifest = harness.load_manifest()
    cfg = run_module._merge(harness.load_config(manifest, "galactica-1.3b"),
                            over["config"])
    job = run_module._merge(harness.load_traffic("train-2k"), over["traffic"])
    return cfg, job


@pytest.fixture(scope="module")
def opt():
    return harness.load_family({"family": "opt"})


def test_the_shared_files_are_the_ones_meant():
    names = {os.path.relpath(p, BENCH) for p in SHARED}
    assert {"serve_driver.py", "train_driver.py", "span_reduce.py", "harness.py",
            "run.py", os.path.join("tools", "aot_compile.py"),
            os.path.join("layer_metrics", "mfu.serve.py"),
            os.path.join("layer_metrics", "mfu.decode.py"),
            os.path.join("layer_metrics", "mfu.train.py"),
            os.path.join("layer_metrics", "idle_unattributed.serve.py"),
            os.path.join("layer_metrics", "decode_step_device_ms.py")} <= names
    assert not any(n.endswith(("flash_decode_roofline.py",
                               "flash_attention_roofline.py")) for n in names)


def _names_of_a_family(source, opt):
    """What `source` names of a family: imports of the weights, of the
    reference's model functions, of the three model counts or of the
    program's models, and a program's trace name as a literal."""
    model_functions = _functions(reference) - SHARED_REFERENCE
    assert {"forward", "block", "served_token_gaps", "loss_and_grads"} <= model_functions
    forbidden = {"reference": model_functions, "flops": MODEL_COUNTS}
    found = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            modules = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            modules = [node.module or ""]
            found += [f"from {node.module} import {a.name}" for a in node.names
                      if a.name in forbidden.get(node.module, ())]
        else:
            modules = []
        found += [f"import {m}" for m in modules if m == "weights"
                  or m.startswith("distributeddeeplearning_tpu.models")]
        if (isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name)
                and node.attr in forbidden.get(node.value.id, ())):
            found.append(f"{node.value.id}.{node.attr}")
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            found += [f"the program name {name!r} as a literal"
                      for name in set(opt.PROGRAMS.values()) | {"jit_"}
                      if name in node.value]
    return found


@pytest.mark.parametrize("path", SHARED, ids=lambda p: os.path.relpath(p, BENCH))
def test_a_shared_file_names_nothing_of_a_family(path, opt):
    with open(path) as f:
        assert not _names_of_a_family(f.read(), opt)


@pytest.mark.parametrize("source, finds", [
    ("import weights", 1),
    ("from weights import make_params", 1),
    ("from distributeddeeplearning_tpu.models.pipelined_transformer import forward", 1),
    ("import distributeddeeplearning_tpu.models.moe as moe", 1),
    ("import flops\nn = flops.matmul_params(cfg)", 1),
    ("from flops import train_token_flops", 1),
    ("import reference\nout = reference.loss_and_grads(p, b, num_heads=4)", 1),
    ('PROGRAM = r"jit__decode_fn"', 2),  # the name, and the `jit_` any such has
    ('name = "jit_other_fn(3)"', 1),
    ("import flops\nimport reference\nflops.roofline_least_seconds(w, p)\n"
     "reference.adamw_step\nfrom distributeddeeplearning_tpu.serve.scheduler "
     "import Request", 0),
])
def test_the_parse_finds_what_it_should(source, finds, opt):
    assert len(_names_of_a_family(source, opt)) == finds


@pytest.mark.parametrize("seed", [11, 2**31 + 3, 2600000237])
def test_opt_hands_out_the_same_weights_bit_for_bit(opt, seed):
    import jax

    cfg, _ = _tiny()
    mine, theirs = opt.make_params(seed, cfg), weights.make_params(seed, cfg)
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(theirs)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
    shapes = opt.param_shapes(cfg)
    assert jax.tree_util.tree_map(lambda s: (s.shape, s.dtype), shapes) == \
        jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), mine)


def test_opt_gives_every_name_its_drivers_need(opt):
    for name in serve_driver.NEEDS + train_driver.NEEDS + ("aot_serve_programs",):
        assert hasattr(opt, name), name
    assert set(opt.PROGRAMS) == {"decode", "prefill_chunk", "train_step"}


def test_opt_counts_as_flops_counts(opt):
    cfg, _ = _tiny()
    assert opt.matmul_params(cfg) == flops.matmul_params(cfg) == \
        2 * (4 * 64 * 64 + 2 * 64 * 128) + 64 * 257
    assert opt.serve_token_flops(cfg, 40) == flops.serve_token_flops(cfg, 40)
    assert opt.train_token_flops(cfg, 64) == flops.train_token_flops(cfg, 64)


def test_opt_reference_is_the_reference(opt):
    import jax.numpy as jnp

    cfg, _ = _tiny()
    heads = cfg["num_attention_heads"]
    params = opt.make_params(5, cfg)
    tokens = jnp.asarray(np.random.default_rng(5).integers(1, 257, (2, 24)), jnp.int32)
    for mine, theirs in zip(
            opt.served_token_gaps(params, tokens[0], cfg, precision="float8"),
            reference.served_token_gaps(params, tokens[0], num_heads=heads,
                                        precision="float8")):
        assert np.array_equal(np.asarray(mine), np.asarray(theirs))
    loss, grads = opt.loss_and_grads(params, tokens, cfg)
    ref_loss, ref_grads = reference.loss_and_grads(params, tokens, num_heads=heads)
    assert float(loss) == float(ref_loss)
    leaves = opt.split_layers(grads)
    assert set(leaves) == {"embed", "pos", "head"} | {
        f"blocks.{name}.{layer}" for name in params["blocks"] for layer in range(2)}
    assert np.array_equal(np.asarray(leaves["blocks.qkv.1"]),
                          np.asarray(ref_grads["blocks"]["qkv"][1]))


def test_opt_builds_the_engine_the_serve_driver_asks_for(opt):
    cfg, _ = _tiny()
    engine, scheduler = opt.build_serve(cfg, opt.make_params(3, cfg))
    geo = cfg["serving"]
    assert engine.prefill_chunk == geo["prefill_chunk"]
    assert engine.page_size == geo["page_size"]
    assert engine.chunk_shapes(3 * geo["prefill_chunk"] + 1)
    engine.reset_stats()
    assert engine.prefix_hit_tokens == 0 and engine.prompt_tokens_seen == 0
    assert engine.decode_impl and engine.kv_dtype
    assert callable(scheduler.run)


def test_opt_builds_the_train_step_from_arrays_and_from_shapes(opt):
    import jax

    cfg, job = _tiny()
    devices = harness.require_chips(1, rehearsal=True)
    params = opt.make_params(3, cfg)
    mesh, step, state = opt.build_train(cfg, job, devices, params)
    assert mesh.devices.size == 1 and callable(step)
    assert jax.tree_util.tree_structure(state.params) == \
        jax.tree_util.tree_structure(params)
    _, _, abstract = opt.build_train(cfg, job, devices, opt.param_shapes(cfg))
    assert not isinstance(jax.tree_util.tree_leaves(abstract.params)[0], jax.Array)
    programs, pool_bytes = opt.aot_serve_programs(cfg, 8, None)
    assert set(programs) == {"decode", "prefill_chunk"} and pool_bytes > 0


def test_a_ctx_made_by_hand_without_a_family_is_read_as_opt(opt):
    """`tests/test_trace_capture.py` (tier 1, older than the seam) hands the
    span readers such a ctx; the drivers always name the family."""
    import types

    assert harness.family_of(types.SimpleNamespace()) is opt
    other = types.SimpleNamespace(PROGRAMS={"decode": "another"})
    assert harness.family_of(types.SimpleNamespace(family=other)) is other


# -- what is missing stops the cell before any device work ----------------------

@pytest.fixture
def no_device(monkeypatch):
    def touched(*a, **k):
        raise AssertionError("the harness reached for a device")

    monkeypatch.setattr(harness, "require_chips", touched)


def _main(tmp_path, cell, config_over):
    over = tmp_path / "over.json"
    over.write_text(json.dumps({"config": config_over}))
    return run_module.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                            "--rehearsal", str(over)])


def test_a_configuration_without_a_family_stops(tmp_path, no_device):
    with pytest.raises(SystemExit, match="names no `family`"):
        _main(tmp_path, "galactica-1.3b.serve-chat", {"family": None})


def test_a_family_with_no_file_stops(tmp_path, no_device):
    with pytest.raises(SystemExit, match=r"'nonesuch'.*families/nonesuch\.py"):
        _main(tmp_path, "galactica-1.3b.serve-chat", {"family": "nonesuch"})


def test_a_family_lacking_what_its_cell_needs_stops(tmp_path, no_device, opt,
                                                    monkeypatch):
    monkeypatch.delattr(opt, "build_train")  # a family that is only served
    with pytest.raises(SystemExit, match=r"'opt'.*`build_train`"):
        _main(tmp_path, "galactica-125m.train-2k", {})
    assert harness.load_family({"family": "opt"}, needs=serve_driver.NEEDS) is opt
