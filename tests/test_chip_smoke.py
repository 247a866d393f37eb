"""``chip_smoke.py`` and the compile-cache rule, checked without a chip.

- the smoke's phase functions (the same code the chip runs) pass at a tiny
  size on the CPU pod, while the script itself refuses to pass without a
  TPU, or without the repo beside it;
- the persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
  says, and otherwise at ONE fixed path inside the checkout — no code path
  sets a directory while the variable is set.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

from distributeddeeplearning_tpu.utils import faults, hardware  # noqa: E402

TINY = chip_smoke.Size(
    num_layers=2, d_model=32, num_heads=2, d_ff=64, vocab_size=96,
    # 8 slots: the dense run shards them over the pod's 8 devices, as it
    # does over a host's four chips
    requests=10, batch_slots=8, prompt_len=12, shared_prefix_len=16,
    max_new_tokens=4, max_seq=64, page_size=8, prefill_chunk=8,
    seq_len=32, batch_size=2, steps_per_epoch=3, epochs=2, preempt_at=4,
    kernel_slots=2, kernel_blocks=4, verify_tokens=3,
)


@pytest.fixture
def restored_cache_config():
    """The phases enable the persistent cache for the process; put the
    config back so the rest of the session compiles as it did."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_smoke_kernel_and_serve_phases_pass_at_tiny_size(
    tmp_path, restored_cache_config
):
    kernels = chip_smoke.run_phase(
        "kernels", TINY, str(tmp_path), on_chip=False
    )
    names = {row["kernel"] for row in kernels["kernels"]}
    assert names == {
        "flash_decode.decode[float32]", "flash_decode.decode[int8]",
        "flash_decode.chunk_prefill[float32]",
        "flash_decode.chunk_prefill[int8]", "flash_decode.verify[float32]",
        "flash_attention.forward", "flash_attention.backward[dq,dk,dv]",
    }
    assert kernels["device"]["platform"] == "cpu"

    serve = chip_smoke.run_phase("serve", TINY, str(tmp_path), on_chip=False)
    assert set(serve["runs"]) == {"paged_f32", "paged_int8", "dense_f32"}
    assert serve["runs"]["dense_f32"]["mesh_devices"] == 8
    assert serve["runs"]["paged_f32"]["mesh_devices"] == 1
    for run in serve["runs"].values():
        assert run["errors"] == 0 and run["generated_tokens"] > 0
    # sharding the slots over 8 devices changes no token
    assert (serve["runs"]["dense_f32"]["token_digest"]
            == serve["runs"]["paged_f32"]["token_digest"])
    # off the chip the report says the twin ran, not the kernel — which is
    # exactly what fails the run on the chip
    stats = dict(serve["runs"]["paged_f32"], platform="tpu")
    with pytest.raises(chip_smoke.SmokeFailure, match="decode ran 'xla'"):
        chip_smoke.check_serve("paged_f32", 0, stats, TINY, on_chip=True)


def test_smoke_train_phases_save_preempt_and_resume(
    tmp_path, monkeypatch, restored_cache_config
):
    # on the data×fsdp mesh: the resume must restore straight into the
    # param shards (a restore into a fresh single-device template comes
    # back committed to one device, and the sharded step refuses it)
    mesh = dict(mesh_flags=["--fsdp", "2"])
    # `ddlt train --faults` exports the plan; keep it out of later tests
    monkeypatch.setenv(faults.ENV_VAR, "")
    try:
        first = chip_smoke.run_phase(
            "train_first", TINY, str(tmp_path), on_chip=False, **mesh
        )
        assert first["checkpoint_step"] == TINY.preempt_at
        resumed = chip_smoke.run_phase(
            "train_resume", TINY, str(tmp_path), on_chip=False, **mesh
        )
    finally:
        monkeypatch.setenv(faults.ENV_VAR, "")
        faults.reset()
    assert resumed["steps"] == TINY.epochs * TINY.steps_per_epoch
    first_loss, last_loss = resumed["epoch_losses"]
    assert last_loss < first_loss


def test_check_serve_fails_on_any_error_finish():
    stats = {
        "finish_reasons": {"length": 4, "error": 1}, "errors": 1,
        "generated_tokens": 16, "prefix_hit_rate": 0.5,
    }
    with pytest.raises(chip_smoke.SmokeFailure, match="finish reasons"):
        chip_smoke.check_serve("paged_f32", 0, stats, TINY, on_chip=False)
    with pytest.raises(chip_smoke.SmokeFailure, match="exited 1"):
        chip_smoke.check_serve("paged_f32", 1, stats, TINY, on_chip=False)


def test_script_refuses_without_a_tpu_and_without_the_repo(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=240, env=env,
    )
    assert out.returncode == chip_smoke.EXIT_NO_ACCELERATOR, out.stderr
    assert '"ok"' not in out.stdout
    assert "no accelerator" in out.stderr

    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    alone = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=60, env=env,
    )
    assert alone.returncode == chip_smoke.EXIT_NO_REPO
    assert alone.stdout == ""


def test_compile_cache_dir_obeys_the_variable_else_fixed_in_checkout(
    monkeypatch, tmp_path, restored_cache_config
):
    default = str(REPO / ".jax_cache")
    assert hardware.DEFAULT_COMPILATION_CACHE_DIR == default

    # placed from outside: nothing in the program sets a directory
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    assert hardware.enable_compilation_cache() == str(tmp_path / "outside")
    assert jax.config.jax_compilation_cache_dir is None
    assert not (tmp_path / "outside").exists()  # JAX makes it, not us

    # not placed: the one fixed path, whatever the cwd / pid / time
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.chdir(tmp_path)
    assert hardware.enable_compilation_cache() == default
    assert jax.config.jax_compilation_cache_dir == default

    # and git ignores it
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored and "chiprun_out/" in ignored


def test_every_entry_point_goes_through_the_one_cache_function():
    """`jax_compilation_cache_dir` is set in exactly one place."""
    hits = []
    for path in [*REPO.glob("*.py"), *(REPO / chip_smoke.PACKAGE).rglob("*.py")]:
        if '"jax_compilation_cache_dir"' in path.read_text():
            hits.append(path.relative_to(REPO).as_posix())
    assert hits == ["distributeddeeplearning_tpu/utils/hardware.py"]
    for entry in ("cli/main.py", "serve/fleet.py"):
        text = (REPO / chip_smoke.PACKAGE / entry).read_text()
        assert "enable_compilation_cache(" in text
    for entry in ("bench.py", "__graft_entry__.py", "chip_smoke.py"):
        assert "enable_compilation_cache(" in (REPO / entry).read_text()
