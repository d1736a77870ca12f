"""Chip capture harness: the dated ``chiprun_out/bench_*.json``
producer (and the ``BENCH_r*.json`` round-artifact body).

Runs ``bench.py`` in a subprocess, takes the last JSON object line of
its stdout (the bench artifact; runtime-warning lines around it are
tolerated), and augments it with:

- ``device_topology`` — platform, device kind, device/host counts, and
  per-device coords/core when the backend exposes them (TPU), so a
  capture documents WHICH chip produced it;
- ``captured_at`` — the UTC timestamp that also names the capture file.

The capture lands in ``chiprun_out/bench_<UTCSTAMP>Z.json`` (the one
directory the chip tool brings back; git-ignored);
``--out`` additionally writes the same body to a named round artifact
(e.g. ``BENCH_r10.json``).  ``--from-json FILE`` skips the bench run
and re-wraps an existing bench stdout capture (for re-stamping a run
taken on a box without this harness).

The engine observatory (pilosa_tpu.perfobs) feeds two more slots:

- ``engine_bw_util`` — MEASURED per-engine achieved bandwidth /
  bw_util from the bench run's own launch samples
  (``extras.perfobs.engines`` in the bench artifact), lifted to the
  top level so a capture answers "which engine ran at what fraction
  of the roof" without digging;
- ``--profile`` — brackets the bench subprocess's run with a device
  trace (the bench process starts/stops it via
  ``PILOSA_TPU_BENCH_PROFILE``; the artifact dir rides the capture's
  ``profile`` slot);
- ``--compare PREV.json`` — per-extras qps / bw_util deltas against a
  previous capture, with >10%-drop regression flags stamped into the
  body and echoed on stderr.

Usage::

    python -m tools.chipcapture [--out BENCH_r10.json]
                                [--from-json FILE] [--timeout SEC]
                                [--profile] [--compare PREV.json]
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CAPTURE_DIR = os.path.join(REPO, "chiprun_out")


def device_topology() -> dict:
    """Platform + per-device identity from the live jax backend.
    Called only AFTER the bench child has exited: a chip belongs to one
    process at a time."""
    import jax

    devs = jax.devices()
    out = {
        "platform": devs[0].platform if devs else None,
        "device_kind": devs[0].device_kind if devs else None,
        "n_devices": len(devs),
        "n_hosts": getattr(jax, "process_count", lambda: 1)(),
    }
    coords = []
    for d in devs:
        ent = {"id": d.id}
        for attr in ("coords", "core_on_chip"):
            v = getattr(d, attr, None)
            if v is not None:
                ent[attr] = list(v) if isinstance(v, tuple) else v
        coords.append(ent)
    out["devices"] = coords
    return out


def last_json_line(text: str) -> dict | None:
    """The last line that parses as a JSON object — bench stdout can
    carry warning lines around the artifact."""
    rec = None
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                rec = json.loads(line)
            except ValueError:
                continue
    return rec


#: A metric dropping by more than this fraction of the previous
#: capture flags a regression in ``--compare``.
REGRESSION_PCT = 10.0

#: Per-extras numeric fields worth comparing across captures: every
#: ``qps*`` variant plus the bandwidth figures.
_COMPARE_FIELDS = ("achieved_gbps_lower", "achieved_gbps", "bw_util")


def _delta(old, new) -> dict | None:
    if not (isinstance(old, (int, float)) and
            isinstance(new, (int, float)) and old):
        return None
    return {"prev": old, "cur": new,
            "delta_pct": round((new - old) / old * 100.0, 2)}


def compare_captures(prev: dict, cur: dict) -> dict:
    """Per-extras qps/bw_util deltas of ``cur`` against a previous
    capture body, with regression flags on qps drops past
    ``REGRESSION_PCT``."""
    out: dict = {"prev_captured_at": prev.get("captured_at"),
                 "regression_threshold_pct": REGRESSION_PCT,
                 "extras": {}, "regressions": []}
    for label, field in (("qps", "value"), ("bw_util", "bw_util")):
        d = _delta(prev.get(field), cur.get(field))
        if d is None:
            continue
        out[label] = d
        if label == "qps" and d["delta_pct"] < -REGRESSION_PCT:
            out["regressions"].append(
                f"headline qps {d['delta_pct']}%")
    for key in sorted(set(prev) & set(cur)):
        pv, cv = prev[key], cur[key]
        if not (isinstance(pv, dict) and isinstance(cv, dict)):
            continue
        ent = {}
        for sub in sorted(set(pv) & set(cv)):
            if not (sub.startswith("qps") or sub in _COMPARE_FIELDS):
                continue
            d = _delta(pv[sub], cv[sub])
            if d is None:
                continue
            ent[sub] = d
            if sub.startswith("qps") and \
                    d["delta_pct"] < -REGRESSION_PCT:
                out["regressions"].append(
                    f"{key}.{sub} {d['delta_pct']}%")
        if ent:
            out["extras"][key] = ent
    return out


def run(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None,
                    help="also write the body to this round artifact "
                         "(e.g. BENCH_r10.json, relative to the repo)")
    ap.add_argument("--from-json", default=None,
                    help="re-wrap an existing bench stdout capture "
                         "instead of running bench.py")
    ap.add_argument("--timeout", type=float, default=1800.0)
    ap.add_argument("--profile", action="store_true",
                    help="bracket the bench run with a device trace "
                         "(artifact dir in the capture's 'profile' "
                         "slot)")
    ap.add_argument("--compare", default=None, metavar="PREV.json",
                    help="stamp per-extras qps/bw_util deltas against "
                         "a previous capture, flagging regressions")
    args = ap.parse_args(argv)

    if args.from_json:
        with open(args.from_json, errors="replace") as fh:
            body = last_json_line(fh.read())
    else:
        env = dict(os.environ)
        if args.profile:
            # the bench process starts/stops the trace itself — a
            # trace opened in THIS process would capture nothing
            env["PILOSA_TPU_BENCH_PROFILE"] = CAPTURE_DIR
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            capture_output=True, text=True, timeout=args.timeout,
            cwd=REPO, env=env)
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr[-4000:])
            return proc.returncode
        body = last_json_line(proc.stdout)
    if body is None:
        print("chipcapture: no JSON artifact found in bench output",
              file=sys.stderr)
        return 1

    stamp = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y%m%dT%H%M%SZ")
    body["captured_at"] = stamp
    body["device_topology"] = device_topology()
    # measured per-engine bw_util from the bench run's own launch
    # samples (perfobs) — analytic bytes / measured walls, not the
    # headline's modeled bytes-per-query
    po = body.get("perfobs")
    if isinstance(po, dict) and isinstance(po.get("engines"), dict):
        body["engine_bw_util"] = {
            eng: s.get("bwUtil")
            for eng, s in po["engines"].items()
            if isinstance(s, dict)}
    if args.compare:
        with open(os.path.join(REPO, args.compare),
                  errors="replace") as fh:
            prev = last_json_line(fh.read())
        if prev is None:
            print(f"chipcapture: no JSON body in {args.compare}",
                  file=sys.stderr)
            return 1
        cmp_out = compare_captures(prev, body)
        body["compare"] = cmp_out
        for r in cmp_out["regressions"]:
            print(f"chipcapture: REGRESSION {r}", file=sys.stderr)

    os.makedirs(CAPTURE_DIR, exist_ok=True)
    cap_path = os.path.join(CAPTURE_DIR, f"bench_{stamp}.json")
    text = json.dumps(body)
    with open(cap_path, "w") as fh:
        fh.write(text + "\n")
    print(cap_path)
    if args.out:
        out_path = os.path.join(REPO, args.out)
        with open(out_path, "w") as fh:
            fh.write(text + "\n")
        print(out_path)
    return 0


if __name__ == "__main__":
    sys.exit(run())
