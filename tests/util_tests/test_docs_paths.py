"""A document may cite only files that exist (ROADMAP D11).

Every backticked word of a scanned document (every word, in the
package's comments and docstrings) that ends in ``.py``, ``.md``,
``.json`` or ``.jsonl`` — and is no wildcard or placeholder — must name
a file of this tree.  One case per document, so a failure
names it.  ``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are history
(they name what was deleted, and when) and are not scanned."""

import functools
import glob
import io
import os
import re
import tokenize

import pytest

_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))

# a path is looked up against the root and against each of these
_BASES = ("", "chainermn_tpu", "docs", "examples", "benchmarks", "tests")

# files a run writes: cited by name, never committed
_WRITTEN_AT_RUN_TIME = {
    "BENCH_HISTORY.json": "the --check sentinel's run history, git-ignored",
    "membership.json": "ElasticMembership's record, beside the checkpoints",
    "trace.json": "TraceRecorder.export's output in a walk-through",
    "perfetto_trace.json": "the jax profiler's own output name",
    "goodput.jsonl": "GoodputReport's log under the trainer's out/",
    "metrics.jsonl": "MetricsExport's log under the trainer's out/",
    "straggler.jsonl": "StragglerReport's log under the trainer's out/",
    "train.py": "the user's own script in a launch line",
}

# ChainerMN's files (SURVEY.md): what a module here stands in for.
# ``chainermn/...`` is the reference's package; these are cited bare.
_THE_REFERENCES = {
    "communicator_base.py", "_communication_utility.py",
    "_memory_utility.py", "point_to_point_communication.py",
    "multi_node_snapshot.py", "shuffle_datablock.py", "empty_dataset.py",
    "examples/imagenet/models/googlenet.py",
    "examples/imagenet/models/resnet50.py",
}

_DOCUMENTS = (
    ["README.md", "MIGRATION.md", "SNIPPETS.md"]
    + sorted(os.path.relpath(p, _ROOT)
             for p in glob.glob(os.path.join(_ROOT, "docs", "*.md")))
    + [".claude/skills/verify/SKILL.md", "chainermn_tpu"])

_CITED = re.compile(r"[\w./~-]+\.(?:py|md|jsonl|json)\b")
_PLACEHOLDER = re.compile(r"[*<>{}$…]|\.\.\.")


@functools.cache
def _basenames():
    """Base names of every file under the looked-up directories (a bare
    ``train_imagenet.py`` names a file wherever it lives in them)."""
    names = set(os.listdir(_ROOT))
    for base in _BASES[1:]:
        for _, _, files in os.walk(os.path.join(_ROOT, base)):
            names.update(files)
    return names


def _resolves(path):
    if "/" not in path:
        return path in _basenames()
    return any(os.path.isfile(os.path.join(_ROOT, base, path))
               for base in _BASES)


def _backticked(text):
    """The words of every backticked span (single or double)."""
    for span in re.findall(r"``([^`\n]+)``|`([^`\n]+)`", text):
        span = span[0] or span[1]
        if not _PLACEHOLDER.search(span):
            yield from span.split()


def _comments_and_docstrings(path):
    with open(path, "rb") as f:
        source = f.read()
    out = []
    for tok in tokenize.tokenize(io.BytesIO(source).readline):
        if tok.type == tokenize.COMMENT or (
                tok.type == tokenize.STRING
                and tok.string.lstrip("rRbBuU").startswith(('"""', "'''"))):
            out.append(tok.string)
    return "\n".join(out)


def _words_of(document):
    """A markdown file's backticked words; every word of the package's
    comments and docstrings, which backtick a file only now and then."""
    if document.endswith(".md"):
        with open(os.path.join(_ROOT, document)) as f:
            return _backticked(f.read())
    return (word for p in sorted(glob.glob(
        os.path.join(_ROOT, document, "**", "*.py"), recursive=True))
        for word in _comments_and_docstrings(p).split()
        if not _PLACEHOLDER.search(word))


@pytest.mark.parametrize("document", _DOCUMENTS)
def test_cites_only_files_that_exist(document):
    missing = set()
    for word in _words_of(document):
        # `path.py::test`, `path.py:149`, `module.py's`: the path part
        for cited in _CITED.findall(word.split("::")[0]):
            if (cited in _WRITTEN_AT_RUN_TIME or cited in _THE_REFERENCES
                    or cited.startswith(("chainermn/", "~", "/"))):
                continue
            if not _resolves(cited.lstrip("./")):
                missing.add(cited)
    assert not missing, (
        f"{document} cites files that are not in the tree: "
        f"{sorted(missing)} — point the sentence at what measures the "
        f"thing now, or take the citation out")
