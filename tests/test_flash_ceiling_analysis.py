"""scripts/flash_ceiling_analysis.py stays importable in a checkout that
holds no assembled long-context artifact (its own committed output went
with the measurement it derived from)."""

import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_import_is_safe_without_artifacts(tmp_path):
    """An artifact-free checkout (fresh clone, CI) must be able to
    import the script — artifact resolution is lazy, from main(); only
    an actual run may SystemExit on a missing assembly."""
    import importlib.util
    import shutil
    import sys

    scripts = tmp_path / "scripts"
    scripts.mkdir()
    src = os.path.join(REPO, "scripts", "flash_ceiling_analysis.py")
    dst = scripts / "flash_ceiling_analysis.py"
    shutil.copy(src, dst)
    # no tmp_path/artifacts dir at all — the empty-checkout case
    spec = importlib.util.spec_from_file_location("fca_bare", str(dst))
    mod = importlib.util.module_from_spec(spec)
    sys.path.insert(0, REPO)  # its REPO points at tmp; the package must
    try:                      # still resolve from the real checkout
        spec.loader.exec_module(mod)  # must NOT raise
        with pytest.raises(SystemExit, match="no assembled"):
            mod._newest_artifact()
    finally:
        sys.path.remove(REPO)
