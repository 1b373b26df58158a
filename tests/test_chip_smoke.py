"""``chip_smoke.py`` at a tiny size on the CPU: its three phases run
end to end and check what they claim, and the script refuses any
backend but a TPU (or a directory without the package) before doing
work."""
import os
import shutil
import subprocess
import sys

import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.kernels import dispatch  # noqa: E402

TINY = chip_smoke.Sizes(n_docs=96, held_out=16, check_docs=12,
                        serve_docs=8, k=16, b=4, batch=8)


@pytest.fixture(autouse=True)
def _clean_dispatch(monkeypatch):
    monkeypatch.delenv(dispatch.ENV_DISPATCH, raising=False)
    dispatch.reset()
    yield
    dispatch.reset()


def test_phases_end_to_end_tiny(tmp_path):
    rec, (rows, _labels, root) = chip_smoke.phase_hash(
        str(tmp_path), TINY, 0, require_kernels=False)
    assert rec["ok"] and rec["docs_in_archive"] == 80
    assert rec["bitwise_checked"]["minwise"] == 12
    assert rec["bitwise_checked"]["oph"] >= 12
    # the tiny corpus is too small for the chip run's accuracy floor
    rec, params = chip_smoke.phase_train(str(tmp_path), TINY, 0, root,
                                         require_kernels=False,
                                         acc_floor=0.0)
    assert rec["ok"] and rec["steps"] >= 8 and rec["restarts"] == 0
    assert rec["examples_seen"] == 80
    rec = chip_smoke.phase_serve(TINY, 0, rows, params,
                                 require_kernels=False)
    assert rec["ok"] and rec["docs"] == TINY.serve_docs
    assert rec["nnz_min"] < rec["nnz_max"]
    assert rec["max_abs_err"] <= chip_smoke.SCORE_ATOL


def test_hash_and_train_on_kernel_arms(tmp_path, monkeypatch):
    """The Pallas arms (interpret mode here) pass the same checks, and
    the records name them."""
    monkeypatch.setenv(dispatch.ENV_DISPATCH,
                       "encode_packed=pallas,logits_packed=kernel")
    rec, (_rows, _labels, root) = chip_smoke.phase_hash(
        str(tmp_path), TINY, 0, require_kernels=False)
    assert rec["arms"]["encode_packed"] == ["pallas"]
    rec, _params = chip_smoke.phase_train(
        str(tmp_path), TINY, 0, root, require_kernels=False,
        acc_floor=0.0)
    assert rec["arms"]["logits_packed"] == ["kernel"]
    assert rec["arms"]["logits_packed_bwd"] == ["kernel"]
    assert rec["arms"]["pallas_mode"] == ["interpret"]


def test_require_names_every_fallback():
    rec = {"phase": "serve", "device": {"platform": "tpu"},
           "arms": {"encode_packed": ["pallas"], "logits_packed": ["unpack"],
                    "pallas_mode": ["compiled"]},
           "tpu_custom_calls": {"serve_score": 0}}
    want = {"encode_packed": "pallas", "logits_packed": "kernel",
            "pallas_mode": "compiled"}
    with pytest.raises(AssertionError, match="logits_packed.*serve_score"):
        chip_smoke._require(rec, want, ("serve_score",))
    rec["arms"]["logits_packed"] = ["kernel"]
    rec["tpu_custom_calls"]["serve_score"] = 2
    chip_smoke._require(rec, want, ("serve_score",))


def test_main_refuses_a_backend_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_script_alone_fails_without_output(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"],
                          cwd=tmp_path, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_compile_cache_follows_env_else_fixed_checkout_dir(monkeypatch,
                                                          tmp_path):
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(compile_cache.ENV_CACHE_DIR)
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
