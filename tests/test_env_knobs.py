"""README "Environment knobs" coverage: every ``SLT_*`` variable the
package or scripts/ read must appear in the README table.
The table is hand-written prose; this grep is what keeps it honest —
add a knob without documenting it and this fails with the name. The
same grep keeps the README's file names honest: a script it tells the
reader to run must be in the tree."""

import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KNOB = re.compile(r"SLT_[A-Z][A-Z0-9_]*")


def _source_files():
    for root in ("split_learning_tpu", "scripts"):
        for dirpath, dirnames, filenames in os.walk(
                os.path.join(REPO, root)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in filenames:
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def test_every_slt_knob_is_documented_in_readme():
    knobs = set()
    for path in _source_files():
        with open(path, encoding="utf-8") as f:
            knobs.update(KNOB.findall(f.read()))
    assert len(knobs) >= 40, sorted(knobs)  # the surface as of PR 13

    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    documented = set(KNOB.findall(readme))
    missing = sorted(knobs - documented)
    assert not missing, (
        "SLT_* knobs read by the code but absent from the README "
        f"'Environment knobs' table: {missing}")


def test_readme_documents_no_phantom_knobs():
    """The inverse direction, looser: a knob named in the README must
    exist somewhere in the tree (tests included — some knobs are
    exercised only there), so renames can't leave stale rows behind."""
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        documented = set(KNOB.findall(f.read()))
    tree = set()
    for path in _source_files():
        with open(path, encoding="utf-8") as f:
            tree.update(KNOB.findall(f.read()))
    for dirpath, dirnames, filenames in os.walk(os.path.join(REPO, "tests")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if fn.endswith(".py"):
                with open(os.path.join(dirpath, fn), encoding="utf-8") as f:
                    tree.update(KNOB.findall(f.read()))
    phantom = sorted(documented - tree)
    assert not phantom, f"README documents knobs nothing reads: {phantom}"


# ``python <path>.py`` commands and every ``scripts/<name>.py``, wherever
# the README names them
_RUN_PY = re.compile(r"python3? +(?:-[A-Za-z]\S* +)*([A-Za-z0-9_./-]+\.py)\b")
_SCRIPT_PY = re.compile(r"\bscripts/[A-Za-z0-9_]+\.py\b")


def test_readme_names_no_file_that_is_gone():
    """Deleting a script leaves its README sentences behind unless
    something looks: every Python file the README tells the reader to
    run, and every ``scripts/`` file it names, exists."""
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    named = set(_RUN_PY.findall(readme)) | set(_SCRIPT_PY.findall(readme))
    assert len(named) >= 5, sorted(named)  # the regexes still match
    gone = sorted(p for p in named
                  if not os.path.exists(os.path.join(REPO, p)))
    assert not gone, f"README.md names files that do not exist: {gone}"
