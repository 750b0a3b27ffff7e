"""Docs stay true: the sweep_engine.md example runs as written (and stays
in sync with its runnable copy), and every relative markdown link
resolves."""

import importlib.util
import re
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

_spec = importlib.util.spec_from_file_location(
    "check_docs", REPO / "scripts" / "check_docs.py")
check_docs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_docs)


def _fenced_python(md: Path) -> list[str]:
    blocks = re.findall(r"```python\n(.*?)```", md.read_text(), re.DOTALL)
    assert blocks, f"no fenced python block in {md}"
    return blocks

# guide -> the runnable example each of its fenced python blocks embeds,
# in document order
EMBEDDED_EXAMPLES = {
    "sweep_engine.md": ["scenario_api.py", "trace_workload.py",
                        "online_drift.py", "sweep_quickstart.py",
                        "user_scaling.py", "edge_cloud.py", "tracing.py"],
    "serving.md": ["serving_gateway.py"],
    "kernels.md": ["moscore_backends.py"],
    "resilience.md": ["fault_injection.py"],
}


def test_guide_examples_match_runnable_copies():
    """Each guide embeds its docs/examples/*.py files verbatim, so the
    'runs as written' guarantee covers the markdown too."""
    for md, examples in EMBEDDED_EXAMPLES.items():
        blocks = _fenced_python(REPO / "docs" / md)
        assert len(blocks) == len(examples), \
            f"{md}: {len(blocks)} python blocks, {len(examples)} examples"
        for block, name in zip(blocks, examples):
            runnable = (REPO / "docs" / "examples" / name).read_text()
            assert block.strip() == runnable.strip(), f"{md} vs {name}"


def test_guide_examples_run():
    for examples in EMBEDDED_EXAMPLES.values():
        for name in examples:
            src = (REPO / "docs" / "examples" / name).read_text()
            exec(compile(src, f"docs/examples/{name}", "exec"), {})


def test_docs_links_resolve():
    assert check_docs.main() == 0
