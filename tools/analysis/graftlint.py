#!/usr/bin/env python
"""graft-lint CLI: AST-lint source trees, jaxpr-audit serving programs.

Usage:
  python tools/analysis/graftlint.py [paths...] [--format json|text]
        [--baseline FILE] [--write-baseline] [--audit-serving]
        [--races] [--prune-baseline] [--no-default-baseline]

Default path is ``paddle_tpu``.  Exit status: 0 when no ERROR-severity
finding survives the baseline, 1 otherwise (2 on usage errors).

``--audit-serving`` additionally builds a tiny CPU LLMEngine (one per
KV dtype: float32 and quantized int8, plus a tp=2 tensor-parallel
engine over forced host devices) and a captured train step and
runs the jaxpr passes over every program they compile — the
donation/transfer/dtype/dead audit of what XLA is really handed.  This
imports jax; plain source linting does not.

``--races`` additionally runs the thread-role/lock-discipline front end
(race_rules.py) — over the explicit paths when given, else over the
multi-threaded host serving stack (paddle_tpu/inference + profiler).
Stdlib-only, and its findings feed the same baseline and exit status.

``--write-baseline`` rewrites the baseline file to accept every finding
of the current run (review the diff before committing it).
``--prune-baseline`` does the inverse hygiene: drops baseline entries
whose fingerprints no longer fire anywhere (only for rule families the
current run exercised — jaxpr entries survive a run without
--audit-serving), printing what was pruned.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _REPO)


def _serving_findings(large_bytes: int):
    """Jaxpr-audit a tiny engine + captured step; returns (findings, report)."""
    # the audit traces programs, it never runs them: the CPU will do
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # the tp=2 audit engine needs two devices; force host devices so the
    # sharded programs trace anywhere (no-op on a real multi-chip host)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            f"{flags} --xla_force_host_platform_device_count=2".strip()

    import jax.numpy as jnp

    import paddle_tpu
    from paddle_tpu.analysis import audit_specs
    from paddle_tpu.analysis.findings import Finding, Location, SEVERITIES
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(vocab=97, hidden=32, layers=2, heads=4, ffn=64,
                           seq=64)
    model = LlamaForCausalLM(cfg)
    engine_kw = dict(max_num_seqs=4, block_size=8, max_model_len=64,
                     max_prefill_tokens=128, prefill_token_bucket=32)
    engine = LLMEngine(model, **engine_kw)
    specs = engine.program_specs(large_bytes=large_bytes)
    # the quantized engine compiles its own program pair (q8 step + q8
    # CoW); its scale pools are large buffers that must be donated too
    q8 = LLMEngine(model, kv_dtype="int8", **engine_kw)
    specs += q8.program_specs(large_bytes=large_bytes)
    # the weight-quantized engine routes every projection/MLP/embedding
    # matmul through the quantized pools (programs suffixed _w8); its
    # int8 pools + f32 scales are the large buffers under audit
    w8 = LLMEngine(model, weight_dtype="int8", **engine_kw)
    specs += w8.program_specs(large_bytes=large_bytes)
    # the tensor-parallel engine lays the same step over a 2-chip mesh
    # (shard_map inside the jit) — its pools are per-shard, its donation
    # contract identical; the audit proves the sharded program is as
    # clean as the single-chip one
    tp2 = LLMEngine(model, tp=2, **engine_kw)
    specs += tp2.program_specs(large_bytes=large_bytes)

    # captured train step: tiny linear regression, donated params
    from paddle_tpu.jit.step import capture_step

    layer = paddle_tpu.nn.Linear(8, 8)
    opt = paddle_tpu.optimizer.SGD(learning_rate=0.1,
                                   parameters=layer.parameters())
    loss_fn = paddle_tpu.nn.MSELoss()

    def train_step(x, y):
        loss = loss_fn(layer(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = capture_step(train_step, models=layer, optimizers=opt)
    x = paddle_tpu.to_tensor(jnp.ones((4, 8), jnp.float32))
    y = paddle_tpu.to_tensor(jnp.zeros((4, 8), jnp.float32))
    specs.append(step.program_spec(x, y, large_bytes=large_bytes))

    report = audit_specs(specs)
    findings = []
    for prog in report["programs"]:
        for d in prog["findings"]:
            findings.append(Finding(
                d["rule"], d["severity"],
                Location(d["file"], d["line"], d["func"]), d["message"],
                trail=tuple(tuple(t) for t in d["trail"])))
    findings.sort(key=lambda f: (SEVERITIES.index(f.severity),
                                 f.location.file, f.rule))
    return findings, report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="graftlint", description=__doc__)
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/dirs to AST-lint (default: paddle_tpu)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON (default: tools/analysis/"
                         "graftlint_baseline.json)")
    ap.add_argument("--no-default-baseline", action="store_true",
                    help="ignore the default baseline file")
    ap.add_argument("--write-baseline", action="store_true",
                    help="accept every current finding into the baseline")
    ap.add_argument("--audit-serving", action="store_true",
                    help="also jaxpr-audit a tiny serving engine + train "
                         "step (imports jax)")
    ap.add_argument("--races", action="store_true",
                    help="also run the thread-role/lock-discipline front "
                         "end (default scope: the inference + profiler "
                         "host serving tiers)")
    ap.add_argument("--prune-baseline", action="store_true",
                    help="drop baseline entries whose fingerprints no "
                         "longer fire (restricted to rule families this "
                         "run exercised); prints what was pruned")
    ap.add_argument("--report-out", default=None,
                    help="with --audit-serving: write the program report "
                         "JSON here")
    ap.add_argument("--large-bytes", type=int, default=1 << 10,
                    help="donation/dead-input 'large buffer' floor for "
                         "--audit-serving (default 1KiB: tiny test model)")
    args = ap.parse_args(argv)

    from paddle_tpu.analysis import (default_baseline_path, filter_baseline,
                                     findings_to_json, format_text,
                                     lint_paths, load_baseline, save_baseline)
    from paddle_tpu.analysis.findings import ERROR, RULES

    paths = args.paths or [os.path.join(_REPO, "paddle_tpu")]
    findings = lint_paths(paths, root=_REPO)
    baseline_path = args.baseline or default_baseline_path()

    race_findings = []
    if args.races:
        from paddle_tpu.analysis.race_rules import (default_race_paths,
                                                    race_lint_paths)
        race_paths = args.paths or default_race_paths(_REPO)
        race_findings = race_lint_paths(race_paths, root=_REPO)
        findings = findings + race_findings

    report = None
    if args.audit_serving:
        jf, report = _serving_findings(args.large_bytes)
        findings = findings + jf

    if args.races and (report is not None or args.report_out):
        baseline = set() if args.no_default_baseline else \
            load_baseline(baseline_path)
        new = filter_baseline(race_findings, baseline)
        by_rule = {}
        for f in race_findings:
            by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
        conc = {
            "paths": sorted(os.path.relpath(p, _REPO) for p in race_paths),
            "findings": len(race_findings),
            "accepted": len(race_findings) - len(new),
            "new": len(new),
            "by_rule": dict(sorted(by_rule.items())),
        }
        report = report if report is not None else {}
        report["concurrency"] = conc
    if report is not None and args.report_out:
        with open(args.report_out, "w") as fp:
            json.dump(report, fp, indent=2)
            fp.write("\n")

    if args.prune_baseline:
        # only prune entries whose rule FAMILY this run exercised: a run
        # without --audit-serving produced no jaxpr findings, so absence
        # there proves nothing
        ran = {"ast"}
        if args.races:
            ran.add("race")
        if args.audit_serving:
            ran.add("jaxpr")
        with open(baseline_path) as fp:
            doc = json.load(fp)
        live = {f.fingerprint for f in findings}
        kept, pruned = [], []
        for e in doc.get("accepted", []):
            tag = RULES.get(e.get("rule", ""), (None, None))[1]
            if tag in ran and e["fingerprint"] not in live:
                pruned.append(e)
            else:
                kept.append(e)
        for e in pruned:
            print(f"pruned {e['fingerprint']}  {e.get('rule', '?'):24s} "
                  f"{e.get('location', '')}")
        if pruned:
            doc["accepted"] = kept
            with open(baseline_path, "w") as fp:
                json.dump(doc, fp, indent=2)
                fp.write("\n")
        print(f"baseline: {len(pruned)} entr{'y' if len(pruned) == 1 else 'ies'} "
              f"pruned, {len(kept)} kept "
              f"(families checked: {'/'.join(sorted(ran))})")
        return 0

    if args.write_baseline:
        save_baseline(baseline_path, findings)
        print(f"baseline written: {baseline_path} "
              f"({len(findings)} accepted)")
        return 0
    if not args.no_default_baseline:
        findings = filter_baseline(findings, load_baseline(baseline_path))

    if args.format == "json":
        print(findings_to_json(findings, baseline=baseline_path))
    else:
        print(format_text(findings))
    return 1 if any(f.severity == ERROR for f in findings) else 0


if __name__ == "__main__":
    sys.exit(main())
