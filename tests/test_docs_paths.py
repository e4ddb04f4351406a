"""The documents that describe the present tree name files that exist.

One case per prose file. Every word inside back-ticks (inline spans and
fenced blocks) that reads as a path of this repo must be there:

  * a word with a `/` whose first component is one of the repo's top-level
    directories names a file or directory (`*`, `<placeholder>` and
    `{a,b}` are matched as globs);
  * a bare file name (`name.py`, `name.json`, `name.md`, ...) is the name
    of some file of the repo.

What is not this repo's is skipped by rule, not by a list: an absolute path
(`/root/reference/...`, `/metrics`), a `path:line` citation (the documents
cite MXNet's tree that way), and the value that follows a `--option` in a
command (the user's own file). History (`CHANGES.md`, `ROADMAP.md`,
`PERF.md`, the round records) is not checked: it names what was; nor is
`chipbench/README.md`, whose paths start at `chipbench/`.
"""
import glob
import os
import re
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "PARITY.md",
             os.path.join(".claude", "skills", "verify", "SKILL.md")] + sorted(
    os.path.join("docs", f) for f in os.listdir(os.path.join(ROOT, "docs"))
    if f.endswith(".md"))
BARE_FILE = re.compile(r"^[A-Za-z_][\w.-]*\.(py|json|jsonl|md|sh|toml|cfg)$")


def repo_files():
    """The repo's files: what git tracks (staged files included); on a
    checkout without git, what is on disk outside dot-directories."""
    try:
        out = subprocess.run(["git", "ls-files"], cwd=ROOT, check=True,
                             capture_output=True, text=True).stdout.split()
        found = [f for f in out if os.path.exists(os.path.join(ROOT, f))]
        if found:
            return found
    except (OSError, subprocess.CalledProcessError):
        pass
    found = []
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        found += [os.path.relpath(os.path.join(base, f), ROOT)
                  for f in files]
    return found


FILES = repo_files()
TOP_DIRS = {f.split("/")[0] for f in FILES if "/" in f}
BASENAMES = {os.path.basename(f) for f in FILES}


def spans(text):
    """The back-ticked spans of a document: fenced blocks line by line,
    then inline spans (a span broken over a line end is joined)."""
    out = []
    for block in re.findall(r"```[^\n]*\n(.*?)```", text, flags=re.S):
        out += block.splitlines()
    text = re.sub(r"```.*?```", "", text, flags=re.S)
    for span in re.findall(r"`([^`]+)`", text):
        joined = re.sub(r"([/_.-])\n\s*", r"\1", span)
        out.append(joined.replace("\n", " "))
    return out


def candidates(span):
    """(word, kind) for the words of a span that read as this repo's
    paths: kind `path` (has a `/`, starts at a top-level directory) or
    `name` (a bare file name)."""
    words, found = span.split(), []
    for i, raw in enumerate(words):
        word = raw.strip("()[],;\"'").rstrip(".:")
        word = word.split("::")[0].rstrip("/")
        if not word or word.startswith("/") or "=" in word \
                or re.search(r":\d+(-\d+)?$", word):
            continue
        follows_option = i > 0 and words[i - 1].startswith("--")
        if "/" in word:
            if word.split("/")[0] in TOP_DIRS \
                    and re.fullmatch(r"[\w./*<>{},-]+", word):
                found.append((word, "path"))
        elif BARE_FILE.match(word) and not follows_option:
            found.append((word, "name"))
    return found


def expand(word):
    """`a/{b,c}/<x>.json` -> ['a/b/*.json', 'a/c/*.json']."""
    m = re.search(r"\{([^{}]*)\}", word)
    if m:
        return [g for alt in m.group(1).split(",")
                for g in expand(word[:m.start()] + alt + word[m.end():])]
    return [re.sub(r"<[^<>]*>", "*", word)]


def missing(text):
    gone = []
    for span in spans(text):
        for word, kind in candidates(span):
            if kind == "name":
                ok = word in BASENAMES
            else:
                ok = all(glob.glob(os.path.join(ROOT, g))
                         for g in expand(word))
            if not ok:
                gone.append(word)
    return sorted(set(gone))


def test_the_rule_reads_paths_and_passes_over_what_is_not_ours():
    text = ("`python nowhere_tool.py --json out.json` and `tests/nope.py`, "
            "`/root/reference/src/engine.cc`, `tests/python/x.py:12`, "
            "`src/operator/nn.cc`, `chipbench/configs/<config>.json`, "
            "`tests/{conftest,test_docs_paths}.py`, `tests/`,\n"
            "`tests/test_docs_\npaths.py::test_x`\n"
            "```\npython tools/no_such_tool.py --quick\n```\n")
    assert missing(text) == ["nowhere_tool.py", "tests/nope.py",
                             "tools/no_such_tool.py"]


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_files_that_exist(doc):
    with open(os.path.join(ROOT, doc)) as f:
        gone = missing(f.read())
    assert not gone, f"{doc} names files the tree does not have: {gone}"
