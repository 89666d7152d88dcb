"""Documents and comments send the reader only to files that are there.

A path that starts with the name of one of the repo's top-level
directories (``tools/...``, ``tests/...``, ``bigdl_tpu/...``,
``benchmark/...``, ``docs/...``, ``examples/...``), in any ``*.md`` or
``*.py``, has to end the path of a file that is there; so has a bare
``name.py`` / ``name.sh`` in a document or the verify skill.  The
history records are exempt: they say what was, and name what went."""

import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: records of what was measured, advised or done, by round or by PR
HISTORY = {"CHANGES.md", "ROADMAP.md", "PERF.md", "ISSUE.md", "REVIEW.md",
           "BASELINE.md", "VERDICT.md", "ADVICE.md", "SURVEY.md",
           "PAPER.md", "PAPERS.md", "SNIPPETS.md"}
#: what building, testing and running leave behind (.gitignore)
SKIP_DIRS = {".git", "chiprun_out", "__pycache__", ".archive_check",
             ".jax_cache", ".pytest_cache", ".hypothesis"}
_TOKEN = re.compile(r"(?<![\w/.-])((?:[\w.-]+/)*[\w-]+\.(?:py|sh))\b")


def _tree():
    files = []
    for d, dirs, names in os.walk(REPO):
        dirs[:] = [x for x in dirs if x not in SKIP_DIRS]
        files += [os.path.relpath(os.path.join(d, n), REPO) for n in names]
    return files


def test_no_document_or_comment_names_a_program_file_that_is_gone():
    files = _tree()
    tops = {f.split(os.sep)[0] for f in files if os.sep in f}
    # every way of naming a file that is there: its path, cut at any "/"
    there = {"/".join(parts[i:]) for parts in (f.split(os.sep) for f in files)
             for i in range(len(parts))}
    missing = {}
    for f in files:
        document = f.endswith(".md")
        if not (document or f.endswith(".py")) or f in HISTORY:
            continue
        with open(os.path.join(REPO, f), encoding="utf-8") as fh:
            text = fh.read()
        for token in _TOKEN.findall(text):
            checked = token.split("/")[0] in tops if "/" in token \
                else document
            if checked and token not in there:
                missing.setdefault(token, set()).add(f)
    assert not missing, "\n".join(
        f"{t}: named in {sorted(fs)}" for t, fs in sorted(missing.items()))
