"""The documents name what exists: every repository path and every
`SPARK_RAPIDS_TPU_*` name written in README.md, docs/*.md and the verify
skill is real, and `config.py`'s table of them is what the package reads.
Plain `re` and `ast` over text; nothing of the package is imported."""
import ast
import glob
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "spark_rapids_tpu")
DOCS = sorted([os.path.join(ROOT, "README.md"),
               os.path.join(ROOT, ".claude", "skills", "verify", "SKILL.md")]
              + glob.glob(os.path.join(ROOT, "docs", "*.md")))

# a path that opens a backticked span (after `python ` where the span is a
# command): under one of the repository's directories, or a bare *.py;
# what follows the path (`::test`, `:123`, ` --flag`) is cut
PATH = re.compile(
    r"`(?:python3? )?((?:(?:tests|tools|ci|examples|chipbench|spark_rapids_tpu)/"
    r"[\w./\-]*\w)|[\w\-]+\.py)(?=[`:\s])")
# a knob, or a family of them written `SPARK_RAPIDS_TPU_SERVING_*`
KNOB = re.compile(r"SPARK_RAPIDS_TPU_[A-Z0-9_]*[A-Z0-9](?:_\*)?")


def _documents():
    assert len(DOCS) >= 13
    for path in DOCS:
        with open(path, encoding="utf-8") as f:
            yield os.path.relpath(path, ROOT), f.read()


def _exists(path: str) -> bool:
    if "/" in path:
        return os.path.exists(os.path.join(ROOT, path))
    # a bare module name: a top-level script, or a module of the package
    # named inside a paragraph about its directory (`keys.py`)
    return os.path.exists(os.path.join(ROOT, path)) or bool(
        glob.glob(os.path.join(PKG, "**", path), recursive=True))


def test_paths_in_documents_exist():
    missing = sorted(
        f"{doc}: {path}" for doc, text in _documents()
        for path in set(PATH.findall(text)) if not _exists(path))
    assert not missing, "\n".join(missing)


def test_knobs_in_documents_are_defined():
    with open(os.path.join(PKG, "config.py"), encoding="utf-8") as f:
        defined = set(KNOB.findall(f.read()))

    def known(name: str) -> bool:
        if name.endswith("_*"):
            return any(d.startswith(name[:-1]) for d in defined)
        return name in defined

    unknown = sorted(
        f"{doc}: {name}" for doc, text in _documents()
        for name in set(KNOB.findall(text)) if not known(name))
    assert not unknown, "\n".join(unknown)


def test_the_knob_table_is_what_the_package_reads():
    """Every `SPARK_RAPIDS_TPU_*` name the package reads has a row in
    `config.py`'s table and every row is read: a name is read where it
    stands whole in a string of the code (what `os.environ` is asked
    for). A name written anywhere else under the package (a docstring, a
    comment, the `SPARK_RAPIDS_TPU_BREAKER_*` of a family) is a row or
    the prefix of one."""
    rows, read, written = [], set(), set()
    row = re.compile(r"\| (SPARK_RAPIDS_TPU_[A-Z0-9_]+) *\|")
    for path in glob.glob(os.path.join(PKG, "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as f:
            text = f.read()
        if path == os.path.join(PKG, "config.py"):
            rows = [m.group(1) for m in map(row.match, text.splitlines())
                    if m]
        read.update(
            n.value for n in ast.walk(ast.parse(text))
            if isinstance(n, ast.Constant) and isinstance(n.value, str)
            and re.fullmatch(r"SPARK_RAPIDS_TPU_[A-Z0-9_]+", n.value))
        written.update(re.findall(r"SPARK_RAPIDS_TPU_[A-Z0-9_]*", text))
    assert len(rows) == len(set(rows)) == 51
    assert read == set(rows), sorted(read ^ set(rows))
    loose = sorted(w for w in written
                   if not any(r.startswith(w) for r in rows))
    assert not loose, loose
