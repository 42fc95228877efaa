"""The documents agree with the tree, and the benchmark's names are all
accounted for.

(a) every repo path a document names exists; (b) every cell,
configuration and metric ``BENCHMARK.json`` declares is named in
``PERF.md``; (c) ``README.md`` states no rate — the numbers live in
``PERF_LEDGER.jsonl`` (the driver's) and ``PERF.md`` (the builders'),
and a rate copied into the README is stale one PR later; (d)
``tools/ci.sh``'s header and its ``[ci] k/N`` lines count the same
steps.  No jax: this file reads text.
"""
import functools
import glob
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (["README.md", "PERF.md", "docs/API.md", "docs/MIGRATION.md"]
             + sorted(os.path.relpath(p, REPO).replace(os.sep, "/")
                      for p in glob.glob(
                          os.path.join(REPO, "docs", "api", "*.md")))
             + ["tools/ci.sh"])

REPO_DIRS = ("apex_tpu/", "benchmarks/", "tests/", "tools/", "docs/",
             "examples/")
ROOT_FILE = re.compile(r"^[A-Za-z_][\w.-]*\.(?:py|jsonl|json|md)$")

# Files a run writes, or that live outside the repo: named in the
# documents, never in the tree.
NOT_IN_THE_TREE = {
    "CLEAN_EXIT.json",            # resilience: the graceful-exit marker
    "trace.chrome.json",          # --trace DIR's Perfetto artefact
    "serve.chrome.json",
    "TESTS_LAST_RUN.json",        # the driver's, outside the repo
    "ISSUE.md", "REVIEW.md",      # the driver's, present per PR
    "config.json",                # a model's published configuration
    "workloads.md",               # the model-configs guide's
    "run.jsonl", "serve.jsonl",   # a run's event log (`--jsonl PATH`)
    "a.py", "b.py",               # `--paths a.py b.py` in a usage line
    "my_train.py",                # a user's driver in an example
}
# What a document's history may cite though it is gone: PERF.md's
# accounts of PR 24 and PR 31 name the substrate that PR 31 deleted.
CITED_AS_HISTORY = {
    "PERF.md": {"bench.py", "tools/bench_gate.py",
                "tools/readme_numbers.py",
                "tests/test_bench_artifact.py"},
}
# A block that cites the reference repo (`ref: setup.py:408`,
# "Reference counterpart: ... `wrap.py`") names that repo's files.
CITES_REFERENCE = re.compile(r"\bref:|\(ref |Reference counterpart")
# Stand-ins in usage lines (`monitor_summary.py RUN.jsonl --chrome
# OUT.json`): upper-case stems and the replica logs r0.jsonl, r1.jsonl.
PLACEHOLDER = re.compile(r"^(?:[A-Z]+|r\d+|serve-r\d+)\.(?:jsonl|json)$")


@functools.lru_cache(maxsize=None)
def _read(doc):
    with open(os.path.join(REPO, doc)) as f:
        return f.read()


@functools.lru_cache(maxsize=None)
def _basenames():
    names = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d not in (
                       "__pycache__", "chiprun_out", "_archive_check",
                       "_bench_scratch")]
        names.update(files)
    return names


def _blocks(text, shell):
    """``(block, is_code)``: paragraphs of prose, whose back-quoted
    spans name things; a table row is a block of its own, and so is a
    line of a fenced example or of the shell script, where every word
    may be an invoked path."""
    fenced, para = False, []
    for line in text.splitlines() + [""]:
        fence = line.lstrip().startswith("```")
        if shell or fenced or fence or not line.strip() \
                or line.lstrip().startswith("|"):
            if para:
                yield "\n".join(para), False
                para = []
            if fence:
                fenced = not fenced
            elif line.strip():
                yield line, shell or fenced
        else:
            para.append(line)


def _candidates(text, shell):
    words = []
    for block, is_code in _blocks(text, shell):
        if CITES_REFERENCE.search(block):
            continue
        if is_code:
            words += block.replace("`", " ").lstrip("# ").split()
        else:
            for span in re.findall(r"`([^`]+)`", block):
                words += span.split()
    for w in words:
        w = w.strip("\"'()[]{},;").rstrip(".:")
        w = w.split("::", 1)[0]               # pytest node ids
        w = w.split("#", 1)[0]                # page.md#anchor
        w = re.sub(r"(?::\d+(?:[-,]\d+)*)+$", "", w)  # f.py:12, :12-14, :3:7
        if w:
            yield w


def _missing(doc):
    basenames = _basenames()
    missing = set()
    for w in _candidates(_read(doc), shell=doc.endswith(".sh")):
        if (any(c in w for c in "<>*{}$|=…") or "XXXXXX" in w
                or w.startswith(("/", "-"))):
            continue            # templates, globs, mktemp, options
        if w.startswith(REPO_DIRS):
            if not os.path.exists(os.path.join(REPO, w)):
                missing.add(w)
        elif ROOT_FILE.match(w):
            # a root-level file, or the bare basename of a file that
            # lives deeper: either must be somewhere in the tree
            if (w not in basenames and w not in NOT_IN_THE_TREE
                    and not PLACEHOLDER.match(w)):
                missing.add(w)
    return sorted(missing - CITED_AS_HISTORY.get(doc, set()))


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_named_paths_exist(doc):
    assert _missing(doc) == [], (
        f"{doc} names files the tree does not hold")


def _benchmark_names():
    bench = json.loads(_read("BENCHMARK.json"))
    return ([("cell", w["name"]) for w in bench["workloads"]]
            + [("config", c["name"]) for c in bench["configs"]]
            + [("end_to_end", m["name"]) for m in bench["end_to_end"]]
            + [("per_layer", m["name"]) for m in bench["per_layer"]])


@pytest.mark.parametrize("kind,name", _benchmark_names())
def test_perf_md_names_what_the_benchmark_declares(kind, name):
    assert re.search(rf"(?<![\w.-]){re.escape(name)}(?![\w-])",
                     _read("PERF.md")), (
        f"PERF.md does not name the {kind} {name}")


def test_readme_states_no_rate():
    readme = _read("README.md")
    rates = re.findall(
        r"\d[\d.,]*\s*[x×]?\s*(?:TF/s|tok/s|tokens/s|img/s)", readme)
    assert rates == []
    assert "BENCH_NUMBERS_START" not in readme


def test_ci_script_counts_its_steps():
    text = _read("tools/ci.sh")
    run = [(int(k), int(n)) for k, n in
           re.findall(r'^echo "\[ci\] (\d+)/(\d+) ', text, flags=re.M)]
    total = len(run)
    assert run == [(k, total) for k in range(1, total + 1)]
    header = [int(k) for k in
              re.findall(r"^#\s{1,3}(\d+)\. \S", text, flags=re.M)]
    assert header == list(range(1, total + 1))
