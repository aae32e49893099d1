"""A traced benchmark run's device time by instruction, under the scopes the program names.

    python3 benchmark/run.py --workload lfm2-24b-a2b-ep8-l5.fed4k --seed 7 --seconds 20 --trace 1
    python3 tools/scope_table.py benchmark/out/lfm2-24b-a2b-ep8-l5.fed4k.7 router dispatch combine
    python3 tools/scope_table.py benchmark/out/olmo-hybrid-7b-fsdp4.fed4k.7 gdn gdn_conv gdn_gates gdn_out dense_mlp attn_out
    python3 tools/scope_table.py benchmark/out/gpt2-medium.fed.7 [--by source]

The first argument is a traced run's stem under a checkout's `benchmark/out/`
(`<cell>.<seed>`, what `--trace 1` leaves there: `<stem>.trace.json` and rank
0's raw `.xplane.pb` under `trace/<stem>.rank0/`), the others the scopes
(`jax.named_scope` components of an `op_name`). For each scope: the busy union
a step of everything under it (what `benchmark/harness/scope_trace.scope_ms`
reads: `moe.router_ms`, `moe.dispatch_ms`, ...), then every device operation of
the traced steps under it, grouped by opcode, result type, the step's phase and
the last two components of its `op_name` (before them, for a pair-streamed flash call, what it
walked and scored: `tiles_<walked>of<all>/keys_<scored>of<walked>`; for a gated delta-rule kernel its `chunk_<C>/heads_<G>of<held>`): calls a step, ms a step (the sum of the calls'
own time over the traced steps, divided by their number), most first. With
`--json PATH` the rows are written there too, each with its instructions' names.

With no scope it prints the step's whole account (`benchmark/harness/step_account.py`, PR 53): every
operation of the traced steps by owner, class and phase (`--by source`: by the Python `file:line` the
trace carries for it), with the flops and bytes XLA counts for it and what part is misfiled.

It reads with the benchmark's own readers (`harness/xplane.py`,
`harness/program_trace.read_xplane`) of the checkout it lies in, so a copy of
the file dropped into an older checkout reads that checkout's run. This is how
the by-instruction tables of PERF.md section 5 are made (PR 36, PR 38). No
benchmark cell and no test runs it.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def table(stem: str, scopes):
    """({scope: busy-union ms a step}, rows) of the traced run at `stem`; a
    row is `{scope, opcode, type, tail, calls, ms, names}`."""
    from statistics import median

    from benchmark.harness import program_trace, xplane

    path = program_trace.raw_trace_path({"summary": {"trace_table": stem + ".trace.json"}})
    if path is None:
        raise SystemExit(f"no raw trace of rank 0 beside {stem}.trace.json")
    trace = xplane.Trace(xplane.extract(path))
    op_names = program_trace.read_xplane(path)["scopes"]
    dev = trace.devices[0]
    runs = trace.step_runs(dev)
    unions, rows = {}, {}
    for scope in scopes:
        mine = [op for op in trace._leaf_ops(dev) if scope in op_names.get(op[0], "").split("/")]
        spans = [(op[4], op[4] + op[5]) for op in mine]
        unions[scope] = median(
            xplane.measure(xplane.union(xplane.clip(spans, start, start + dur)))
            for _, _, start, dur in runs) / 1e6 if mine and runs else None
        for op_name, opcode, target, rtype, start, dur in mine:
            if not any(s <= start and start + dur <= s + d for _, _, s, d in runs):
                continue
            parts = op_names[op_name].split("/")
            # A pair-streamed flash call says what it walked and scored (`tiles_<walked>of<all>`, `keys_<scored>of<walked>`),
            # a gated delta-rule kernel the positions of its chunk and the heads a program walks (`chunk_<C>`, `heads_<G>of<held>`).
            walk = [part for part in parts[:-2] if re.fullmatch(r"(tiles|keys|heads)_\d+of\d+|chunk_\d+", part)]
            tail = program_trace.phase(op_names[op_name]) + " " + "/".join(walk + parts[-2:])
            row = rows.setdefault((scope, target or opcode, rtype, tail), {
                "scope": scope, "opcode": target or opcode, "type": rtype, "tail": tail,
                "calls": 0, "ms": 0.0, "names": set()})
            row["calls"] += 1 / len(runs)
            row["ms"] += dur / 1e6 / len(runs)
            row["names"].add(op_name)
    rows = sorted(rows.values(), key=lambda r: (scopes.index(r["scope"]), -r["ms"]))
    return unions, [{**r, "names": sorted(r["names"])} for r in rows]


def account(stem: str, by: str):
    """The rows of `step_account.Account.rows` for the traced run at `stem`, all of them."""
    from benchmark.harness import program_trace, step_account, xplane
    from benchmark.harness.peaks import peaks_for

    run = {"summary": {"trace_table": stem + ".trace.json"}}
    path = program_trace.raw_trace_path(run)
    if path is None:
        raise SystemExit(f"no raw trace of rank 0 beside {stem}.trace.json")
    with open(stem + ".trace.json") as fh:
        trace = xplane.Trace(json.load(fh))
    with open(stem + ".json") as fh:  # the run's record: which device's peaks the floor and XLA's byte count divide by
        peaks = peaks_for(json.load(fh)["summary"]["device"]["kind"])
    mine = step_account.Account(trace, step_account.read(path), peaks)
    rows = mine.rows(by)
    step_account.print_rows(rows, by)
    print(f"classes ms/step {json.dumps(mine.classes)} of step.device_ms {trace.step_device_ms()}; "
          f"unowned {mine.unowned_ms()} misfiled {mine.misfiled_ms()} product floor {mine.product_floor_ms()}")
    return {"classes": mine.classes, "rows": rows}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("stem", help="benchmark/out/<cell>.<seed> of a --trace 1 run")
    ap.add_argument("scopes", nargs="*", help="none: the step's whole account")
    ap.add_argument("--by", choices=("owner", "source"), default="owner", help="the account's rows, with no scope")
    ap.add_argument("--json", help="also write {unions, rows} (the account's {classes, rows}) here")
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    if not args.scopes:
        out = account(args.stem, args.by)
        if args.json:
            with open(args.json, "w") as fh:
                json.dump(out, fh)
        return
    unions, rows = table(args.stem, args.scopes)
    for scope in args.scopes:
        mine = [r for r in rows if r["scope"] == scope]
        print(f"== {scope}: busy union {unions[scope]} ms a step, "
              f"sum of calls {sum(r['ms'] for r in mine):.3f}")
        for r in mine:
            print(f"{r['ms']:9.3f} ms {r['calls']:6.1f} x {r['opcode']:<16} {r['type']:<22} "
                  f"{r['tail']}  [{', '.join(r['names'][:3])}{', ...' if len(r['names']) > 3 else ''}]")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"unions": unions, "rows": rows}, fh)


if __name__ == "__main__":
    main()
