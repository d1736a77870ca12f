"""Executable documentation checker.

Walks a markdown file for ` ```sh ` blocks (replaying their
`curl -XPOST localhost:10101/...` lines) and ` ```pql ` blocks
(executed against the current `<!-- doctest index: NAME -->` context);
a ` ```response ` block immediately following a pql block asserts the
exact JSON `results` payload.  Run by `tests/test_docs.py` against a
fresh in-process server per file, so every example in the docs is a
tested example (the VERDICT #8 contract; reference
docs/query-language.md:57-905 is the coverage bar).

`--fill` rewrites the response blocks in place with the actual
results — the authoring loop: write examples, fill, review the diff,
commit; the test then pins them forever.
"""

from __future__ import annotations

import json
import re
import sys

_MARKER = re.compile(r"<!--\s*doctest index:\s*(\S+)\s*-->")
_CURL = re.compile(r"curl\s+-XPOST\s+localhost:10101(/\S+)")
_BODY = re.compile(r"-d\s+'([^']*)'")


def parse(text: str):
    """-> list of events:
    ("post", path, body_or_None)           — replayed sh curl
    ("query", index, pql, expected_or_None, response_span) — pql block;
      response_span = (start_line, end_line) of the response BODY for
      --fill rewriting, or None when no response block follows."""
    lines = text.splitlines()
    events = []
    index = None
    i = 0
    while i < len(lines):
        stripped = lines[i].strip()
        m = _MARKER.match(stripped)
        if m:
            index = m.group(1)
            i += 1
            continue
        if stripped == "```sh":
            i += 1
            block = []
            while i < len(lines) and lines[i].strip() != "```":
                block.append(lines[i])
                i += 1
            joined, cur = [], ""
            for ln in block:
                if ln.rstrip().endswith("\\"):
                    cur += ln.rstrip()[:-1] + " "
                else:
                    joined.append(cur + ln)
                    cur = ""
            for cmd in joined:
                mc = _CURL.search(cmd)
                if mc:
                    mb = _BODY.search(cmd)
                    events.append(("post", mc.group(1),
                                   mb.group(1) if mb else None))
        elif stripped == "```pql":
            i += 1
            pql_lines = []
            while i < len(lines) and lines[i].strip() != "```":
                pql_lines.append(lines[i])
                i += 1
            i += 1  # closing fence
            # optional response block directly after (blank lines ok)
            j = i
            while j < len(lines) and not lines[j].strip():
                j += 1
            expected = None
            span = None
            if j < len(lines) and lines[j].strip() == "```response":
                start = j + 1
                j += 1
                resp_lines = []
                while j < len(lines) and lines[j].strip() != "```":
                    resp_lines.append(lines[j])
                    j += 1
                span = (start, j)  # body lines [start, j)
                expected = "\n".join(resp_lines)
                i = j
            if index is None:
                raise SystemExit(
                    "pql block before any doctest index marker")
            events.append(("query", index,
                           "\n".join(pql_lines).strip(), expected, span))
            if span is None:
                # i already points at the first line AFTER the pql
                # fence; the loop-bottom increment would skip it
                continue
        i += 1
    return events


def run(path: str, fill: bool = False) -> int:
    """Execute one doc's examples against a fresh in-process server.
    Returns the number of verified examples; raises on mismatch."""
    import contextlib
    import tempfile
    import urllib.request

    from pilosa_tpu.server.server import Server

    text = open(path).read()
    events = parse(text)
    stack = contextlib.ExitStack()
    data_dir = stack.enter_context(tempfile.TemporaryDirectory())
    srv = Server(data_dir, host="127.0.0.1", port=0)
    srv.open()
    rewrites: list[tuple[tuple[int, int], str]] = []
    checked = 0
    try:
        for ev in events:
            if ev[0] == "post":
                _, p, body = ev
                data = (body or "").encode() or None
                req = urllib.request.Request(srv.uri + p, data=data,
                                             method="POST")
                if body and body.lstrip().startswith("{"):
                    req.add_header("Content-Type", "application/json")
                with urllib.request.urlopen(req) as resp:
                    resp.read()
                continue
            _, index, pql, expected, span = ev
            req = urllib.request.Request(
                srv.uri + f"/index/{index}/query",
                data=pql.encode(), method="POST")
            with urllib.request.urlopen(req) as resp:
                got = json.loads(resp.read())["results"]
            if fill and span is not None:
                rewrites.append((span, json.dumps(got, sort_keys=True)))
                continue
            if expected is not None:
                want = json.loads(expected)
                if got != want:
                    raise AssertionError(
                        f"{path}: example {pql!r} returned\n  {got}\n"
                        f"expected\n  {want}")
                checked += 1
    finally:
        srv.close()
        stack.close()
    if fill and rewrites:
        lines = text.splitlines()
        for (start, end), payload in reversed(rewrites):
            lines[start:end] = [payload]
        open(path, "w").write("\n".join(lines) + "\n")
        print(f"{path}: filled {len(rewrites)} response blocks")
    return checked


def main(argv) -> int:
    fill = "--fill" in argv
    files = [a for a in argv if not a.startswith("--")]
    for f in files:
        n = run(f, fill=fill)
        if not fill:
            print(f"{f}: {n} examples verified")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
