"""``ci.sh`` names modules (``python -m distributed_tensorflow_tpu.<module>``)
and files of the repository (test files, documents, a Makefile's directory).
Each must exist: a leg that runs a module that was renamed, or guards a
file that is gone, fails here and not in a CI run nobody reads."""

import importlib.util
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "ci.sh")) as fh:
    CI = fh.read()

MODULES = sorted(set(re.findall(
    r"python3? -m (distributed_tensorflow_tpu(?:\.\w+)+)", CI)))

# A path of the repository: it starts at one of the checkout's directories
# (not inside a "$TMP/..." path) and ends in a source or document suffix;
# what a build leaves behind (.so, the sanitizer binaries) is not named so.
PATHS = sorted(set(re.findall(
    r"(?<![\w$/.-])((?:tests|docs|examples|perfbench|src|"
    r"distributed_tensorflow_tpu)/[\w./-]*\.(?:py|md|sh|cc|json|toml))\b",
    CI)) | set(re.findall(r"make -C (\S+)", CI)) | {"pyproject.toml"})


def test_ci_script_names_something_to_check():
    assert "distributed_tensorflow_tpu.train" in MODULES
    assert "tests/test_chaos.py" in PATHS
    assert "distributed_tensorflow_tpu/csrc/coordination" in PATHS


@pytest.mark.parametrize("module", MODULES)
def test_module_run_by_ci_resolves(module):
    assert importlib.util.find_spec(module) is not None


@pytest.mark.parametrize("path", PATHS)
def test_path_named_by_ci_exists(path):
    assert os.path.exists(os.path.join(REPO, path))
