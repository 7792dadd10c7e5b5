"""The tree's options and commands, counted against the documents that list
them: an environment name that is read but not in the README's table, a
command the README shows that no longer exists, a file a Makefile recipe runs
that is gone, each fails here and not in a reader's shell."""

import re
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
NAME = re.compile(r"TPUCFN_[A-Z0-9_]+")
# where the program reads its environment (not tests: they set names, and
# TPUCFN_REGEN_GOLDENS is theirs alone)
PROGRAM = ("tpucfn", "examples", "benches", "chip_smoke.py",
           "__graft_entry__.py")


def program_files():
    for top in PROGRAM:
        p = REPO / top
        yield from ([p] if p.is_file() else sorted(p.rglob("*.py")))


def fenced_blocks(text: str) -> list[str]:
    return re.findall(r"^```[a-z]*\n(.*?)^```", text, flags=re.S | re.M)


def test_every_environment_name_is_in_the_readmes_table():
    read = {n for f in program_files() for n in NAME.findall(f.read_text())}
    readme = (REPO / "README.md").read_text()
    section = readme[readme.index("**Environment names.**"):]
    listed = [m.group(1) for m in re.finditer(
        r"^\| `(TPUCFN_[A-Z0-9_]+)` \| \S", section, flags=re.M)]
    assert len(listed) == len(set(listed)), "a name is listed twice"
    assert read - set(listed) == set(), "read by the program, not in the table"
    assert set(listed) - read == set(), "in the table, read by nothing"
    assert len(listed) == 46  # the count ROADMAP D10 asks every PR to state


def test_every_command_the_readme_and_the_makefile_show_exists():
    readme = (REPO / "README.md").read_text()
    makefile = (REPO / "Makefile").read_text()
    targets = set(re.findall(r"^([a-z][a-z0-9-]*):", makefile, flags=re.M))
    file_of = re.compile(r"\bpython3? ([\w./-]+\.py)\b")
    module_of = re.compile(r"\bpython3? -m ((?:tpucfn|benchmark)[\w.]*)")
    files, modules, shown_targets = set(file_of.findall(makefile)), set(), set()
    assert files, "the Makefile's recipes run no file?"
    for block in fenced_blocks(readme):
        files |= set(file_of.findall(block))
        modules |= set(module_of.findall(block))
        shown_targets |= set(re.findall(r"\bmake ([a-z][a-z0-9-]*)", block))
    assert {"chip_smoke.py", "benches/gdn_bench.py"} <= files
    assert "benchmark.run" in modules and "tier1" in shown_targets
    missing = sorted(f for f in files if not (REPO / f).is_file())
    missing += sorted(
        m for m in modules
        if not ((REPO / (m.replace(".", "/") + ".py")).is_file()
                or (REPO / m.replace(".", "/") / "__init__.py").is_file()))
    assert missing == [], f"shown or run, but not in the tree: {missing}"
    assert shown_targets - targets == set(), "a target the Makefile lacks"
