"""What the repository says about itself must exist.

A deletion of a few thousand lines breaks nothing the other suites see:
a document goes on naming a script that is gone, a make recipe a test
file, a CI job a target. These cases read the documents, the Makefile
and the workflow as text and hold every path, `make` target and
`python -m` module they name against the tree. Plain Python, no `jax`:
cases are found by glob at collection and take milliseconds each.
"""

import ast
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Directories whose files a document, a recipe or a job may name by
#: their path from the root of the repository.
ROOTS = ("horovod_tpu", "benchmark", "scripts", "tests", "examples", "docs")

#: `PERF.md`, `ROADMAP.md`, `CHANGES.md`, `VERDICT.md`, `ADVICE.md` hold
#: history, and history may name what is gone: they are not cases.
DOCUMENTS = sorted(
    glob.glob(os.path.join(REPO, "docs", "*.md"))
    + [os.path.join(REPO, "README.md"),
       os.path.join(REPO, ".claude", "skills", "verify", "SKILL.md")])

_PATH = re.compile(
    r"(?<![\w/.<-])((?:%s)/[\w./-]*|[\w-]+\.py)" % "|".join(ROOTS))
_MODULE = re.compile(
    r"python3? -m ((?:horovod_tpu|benchmark)(?:\.\w+)*)")
#: A target in a document is `make X` in a code span or on a line of a
#: code block that starts with it; "and make timeline diagnostics
#: ambiguous" is prose.
_DOC_MAKE = re.compile(r"(?:`|^\s*)make ([a-z][a-z0-9-]*)", re.M)


def _read(path):
    with open(path, encoding="utf-8") as f:
        return f.read()


def _rel(path):
    return os.path.relpath(path, REPO)


def _every_file_name():
    names = set(os.listdir(REPO))
    for root in ROOTS:
        for _, _, files in os.walk(os.path.join(REPO, root)):
            names.update(files)
    return names


FILE_NAMES = _every_file_name()


def _module_exists(module):
    base = os.path.join(REPO, *module.split("."))
    return (os.path.isfile(base + ".py")
            or os.path.isfile(os.path.join(base, "__main__.py")))


def _dangling(text):
    """The paths and `python -m` modules `text` names that the tree does
    not hold. A pattern (`tests/test_*.py`, `benchmark/<dir>/`) is not a
    path; `docs/x.rst` is the reference's file, not ours; a bare
    `name.py` may sit in any directory; `train.py` and `my_*.py` stand
    for the user's script."""
    out = [f"python -m {m}" for m in _MODULE.findall(text)
           if not _module_exists(m)]
    for m in _PATH.finditer(text):
        if text[m.end():m.end() + 1] in ("*", "<", "{", "[", "$"):
            continue
        if "/" not in m.group(1):
            placeholder = (m.group(1) == "train.py"
                           or m.group(1).startswith("my_"))
            if not placeholder and m.group(1) not in FILE_NAMES:
                out.append(f"path {m.group(1)}")
            continue
        path = m.group(1).rstrip(".:/-")
        if path.startswith("docs/") and not path.endswith(".md"):
            continue
        if not os.path.exists(os.path.join(REPO, path)):
            out.append(f"path {path}")
    return out


def _makefile():
    """{target: (prerequisites, recipe text)}, variables expanded."""
    text = _read(os.path.join(REPO, "Makefile")).replace("\\\n", " ")
    variables = dict(re.findall(r"^(\w+)\s*[?:]?=\s*(.*)$", text, re.M))
    for _ in range(3):  # a variable may name another
        text = re.sub(r"\$\((\w+)\)",
                      lambda m: variables.get(m.group(1), ""), text)
    targets = {}
    current = None
    for line in text.split("\n"):
        m = re.match(r"^([A-Za-z0-9_-]+):(?!=)(.*)$", line)
        if m:
            current = m.group(1)
            targets[current] = (m.group(2).split(), [])
        elif line.startswith("\t") and current:
            targets[current][1].append(line)
        elif line.strip() and not line.startswith("#"):
            current = None
    return {t: (pre, "\n".join(rec)) for t, (pre, rec) in targets.items()}


MAKE_TARGETS = _makefile()


def _ci_jobs():
    """{job: its block of the workflow}, read without a YAML package:
    a job is a key indented by two spaces under `jobs:`."""
    text = _read(os.path.join(REPO, ".github", "workflows", "test.yml"))
    body = text[text.index("\njobs:\n") + len("\njobs:\n"):]
    jobs, current = {}, None
    for line in body.split("\n"):
        m = re.match(r"^  ([A-Za-z0-9_-]+):\s*$", line)
        if m:
            current = m.group(1)
            jobs[current] = []
        elif current is not None:
            jobs[current].append(line)
    return {j: "\n".join(lines) for j, lines in jobs.items()}


CI_JOBS = _ci_jobs()


@pytest.mark.parametrize("doc", DOCUMENTS, ids=_rel)
def test_document_names_only_what_exists(doc):
    text = _read(doc)
    wrong = _dangling(text) + [f"make {t}" for t in _DOC_MAKE.findall(text)
                               if t not in MAKE_TARGETS]
    assert not wrong, f"{_rel(doc)} names what does not exist: {wrong}"


@pytest.mark.parametrize("target", sorted(MAKE_TARGETS))
def test_make_target_uses_only_what_exists(target):
    prerequisites, recipe = MAKE_TARGETS[target]
    wrong = _dangling(recipe) + [f"prerequisite {p}" for p in prerequisites
                                 if p not in MAKE_TARGETS]
    assert not wrong, f"make {target} uses what does not exist: {wrong}"


@pytest.mark.parametrize("job", sorted(CI_JOBS))
def test_ci_job_calls_a_make_target_that_exists(job):
    block = CI_JOBS[job]
    wrong = [f"make {t}" for t in re.findall(r"\bmake ([a-z0-9-]+)", block)
             if t not in MAKE_TARGETS]
    for needs in re.findall(r"^\s+needs:\s*(.+)$", block, re.M):
        wrong += [f"needs {n}" for n in re.findall(r"[\w-]+", needs)
                  if n not in CI_JOBS]
    assert not wrong, f"CI job {job} calls what does not exist: {wrong}"


#: The modules PR 28 deleted and the names it took out of
#: `horovod_tpu.profiler.flops`.
GONE = {
    "bench", "perf_gate", "horovod_tpu.observability.perfboard",
    "RESNET_FWD_GMACS", "INCEPTION_V3_FWD_GMACS", "VGG16_FWD_GMACS",
    "TRAIN_STEP_MULTIPLIER", "HBM_GIB", "hbm_bytes_per_chip",
    "resnet_train_flops_per_image", "inception_v3_train_flops_per_image",
    "vgg16_train_flops_per_image", "transformer_train_flops_per_token",
    "transformer_matmul_params", "pick_flops", "xla_flops_enabled"}


def _python_files():
    files = glob.glob(os.path.join(REPO, "*.py"))
    for root in ROOTS:
        files += glob.glob(os.path.join(REPO, root, "**", "*.py"),
                           recursive=True)
    return sorted(files)


def _names_used(node):
    """The names `node` imports, reads off a module, or hands to
    `importlib` as a string."""
    if isinstance(node, ast.Import):
        return [a.name for a in node.names]
    if isinstance(node, ast.ImportFrom):
        return [node.module or ""] + [
            n for a in node.names for n in (a.name,
                                            f"{node.module}.{a.name}")]
    if isinstance(node, ast.Attribute):
        return [node.attr]
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    return []


def test_no_module_imports_what_is_gone():
    wrong = []
    for path in _python_files():
        if os.path.samefile(path, __file__):
            continue
        for node in ast.walk(ast.parse(_read(path), path)):
            gone = GONE.intersection(_names_used(node))
            if gone:
                wrong.append(f"{_rel(path)}:{node.lineno} {sorted(gone)}")
    assert not wrong, f"uses of what PR 28 deleted: {wrong}"
