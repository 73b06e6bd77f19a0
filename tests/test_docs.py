"""The documented surface runs as written: the ```python blocks of the
README and docs/api.md (through tools/run_doc_snippets.py, one shared
namespace per file) and the scripts under examples/."""
import importlib.util
import pathlib
import runpy

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _run_doc_snippets():
    spec = importlib.util.spec_from_file_location(
        "run_doc_snippets", ROOT / "tools" / "run_doc_snippets.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.timeout(300)
@pytest.mark.parametrize("doc", ["README.md", "docs/api.md"])
def test_doc_snippets_run(doc):
    assert _run_doc_snippets().run_file(ROOT / doc) > 0


@pytest.mark.timeout(300)
@pytest.mark.parametrize("script", ["quickstart.py", "temporal_analytics.py"])
def test_example_runs(script):
    runpy.run_path(str(ROOT / "examples" / script), run_name="__main__")
