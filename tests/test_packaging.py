"""Packaging metadata (pyproject.toml) — the installable-unit analog
of the reference's pinned requirements + container build (reference
src/requirements.txt:1-15, src/Dockerfile:1-63): a user must be able
to build/install this framework as a wheel and get the CLI, every
subpackage, and the native codec source."""

import glob
import os
import subprocess
import sys
import tomllib

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _meta():
    with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
        return tomllib.load(f)


def test_version_single_source():
    """The version is dynamic from version.py — no second copy that
    can drift."""
    meta = _meta()
    assert "version" in meta["project"]["dynamic"]
    attr = meta["tool"]["setuptools"]["dynamic"]["version"]["attr"]
    mod_path, attr_name = attr.rsplit(".", 1)
    import importlib
    assert getattr(importlib.import_module(mod_path), attr_name)


def test_console_entry_point_resolves():
    """`slt` must point at a real callable."""
    target = _meta()["project"]["scripts"]["slt"]
    mod_path, func = target.split(":")
    import importlib
    assert callable(getattr(importlib.import_module(mod_path), func))


def test_native_codec_source_ships():
    """The C++ codec compiles on first use from shipped SOURCE
    (native/codec.py); a wheel without the .cc would silently
    downgrade every install to the NumPy fallback."""
    pdata = _meta()["tool"]["setuptools"]["package-data"]
    assert "*.cc" in pdata["split_learning_tpu.native"]
    assert os.path.exists(os.path.join(
        REPO, "split_learning_tpu", "native", "slt_codec.cc"))


def test_runtime_deps_are_baked_in_set():
    """Import-time deps must be the always-available core (the gated
    integrations — mlflow/boto3/torchvision — belong in extras, per
    the fallback discipline the runtime tests pin)."""
    meta = _meta()
    names = {d.split(">")[0].split("=")[0].strip()
             for d in meta["project"]["dependencies"]}
    assert {"jax", "flax", "optax", "numpy"} <= names
    for gated in ("mlflow", "boto3", "torchvision", "fastapi"):
        assert gated not in names
    extras = meta["project"]["optional-dependencies"]
    assert any("mlflow" in d for d in extras.get("mlflow", []))
    assert any("boto3" in d for d in extras.get("s3", []))


@pytest.mark.slow
def test_wheel_builds_offline_and_is_complete(tmp_path):
    """End to end: `pip wheel --no-index` (offline, ambient
    setuptools) must produce a wheel containing every subpackage, the
    native source, and importable metadata."""
    out = subprocess.run(
        [sys.executable, "-m", "pip", "wheel", "--no-build-isolation",
         "--no-deps", "--no-index", "-q", "-w", str(tmp_path), REPO],
        capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-800:]
    whl = glob.glob(str(tmp_path / "*.whl"))
    assert len(whl) == 1
    import zipfile
    names = zipfile.ZipFile(whl[0]).namelist()
    subpkgs = {n.split("/")[1] for n in names
               if n.startswith("split_learning_tpu/") and "/" in n}
    for pkg in ("core", "data", "launch", "models", "native", "ops",
                "parallel", "runtime", "tracking", "transport", "utils"):
        assert pkg in subpkgs, f"wheel missing subpackage {pkg}"
    assert "split_learning_tpu/native/slt_codec.cc" in names
