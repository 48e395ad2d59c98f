"""Split-inference serving CLI — a thin shell over ``repro.api.run``.

The workload is one :class:`repro.api.ServeSpec`; the CLI loads it from
``--config serve.json``, applies dotted ``--set key=value`` overrides, and
hands it to the runner (spec → registered engine + scheduling stack →
ServeReport). The default engine is the continuous-batching runtime
(repro.runtime): a global admission controller holds the per-step decode
token budget fixed — the GPSL invariant applied to serving. A few legacy
convenience flags (``--requests``, ``--budget``, ``--static``, …) map onto
spec overrides so existing invocations keep working.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --requests 8 --prompt-len 32 --max-new 16 --budget 8
  PYTHONPATH=src python -m repro.launch.serve --config serve.json \
      --set scheduler.policy=ljf --set workload.num_requests=64
  ... --static            # static-batch A/B engine (engine.name=static)
  ... --no-reduced        # full-size architecture
  ... --speculative --draft-layers 2 --gamma 4   # speculative decoding
  ... --stream tokens.jsonl                      # token streaming sink
"""
from __future__ import annotations

import argparse
from typing import List

from repro import api
from repro.launch.compile_cache import enable_compile_cache
# legacy re-exports: the static engine moved into the runtime package
from repro.runtime.static import BatchedServer, Request  # noqa: F401


def default_serve_spec() -> api.ServeSpec:
    """The CLI's baseline spec: reduced granite, 8 requests, budget 8."""
    return api.ServeSpec(
        model=api.ModelSpec(arch="granite-3-2b", reduced=True))


def _legacy_overrides(args) -> List[str]:
    """Map the convenience flags onto dotted spec overrides."""
    sets: List[str] = []

    def add(key, value):
        if value is not None:
            sets.append(f"{key}={value}")

    add("model.arch", args.arch)
    if args.reduced is not None:        # tri-state: --reduced/--no-reduced
        add("model.reduced", "true" if args.reduced else "false")
    if args.static:
        add("engine.name", "static")
    if args.paged:
        add("engine.name", "paged")
    if args.speculative:
        add("engine.name", "speculative")
    add("cache.page_size", args.page_size)
    add("cache.num_pages", args.num_pages)
    add("draft.num_layers", args.draft_layers)
    add("draft.arch", args.draft_arch)
    add("draft.gamma", args.gamma)
    if args.stream is not None:
        add("stream.enabled", "true")
        if args.stream:
            add("stream.path", args.stream)
    add("sampling.method", "sample" if args.sample else None)
    add("sampling.temperature", args.temperature)
    add("sampling.top_k", args.top_k)
    add("sampling.top_p", args.top_p)
    add("workload.num_requests", args.requests)
    if args.prompt_len is not None:
        add("workload.prompt_lens", f"[{args.prompt_len}]")
    if args.max_new is not None:
        add("workload.max_new_tokens", f"[{args.max_new}]")
    add("admission.token_budget", args.budget)
    add("scheduler.policy", args.policy)
    add("report.verify", args.verify)
    add("checkpoint", args.checkpoint)
    if args.seed is not None:
        add("engine.seed", args.seed)
        add("workload.seed", args.seed)
    return sets


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default=None, metavar="SERVE_JSON",
                    help="ServeSpec JSON file (see docs/api.md)")
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    dest="sets",
                    help="dotted spec override, e.g. scheduler.policy=ljf "
                         "or workload.prompt_lens=[8,64] (repeatable)")
    ap.add_argument("--print-spec", action="store_true",
                    help="print the resolved spec JSON and exit")
    # legacy convenience flags (all map onto --set overrides)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="smoke-size architecture (--no-reduced for full)")
    ap.add_argument("--static", action="store_true",
                    help="use the static-batch engine instead of the "
                         "continuous runtime")
    ap.add_argument("--paged", action="store_true",
                    help="use the paged-KV engine (engine.name=paged): "
                         "page-granular cache allocation, same admission "
                         "invariant")
    ap.add_argument("--page-size", type=int, default=None,
                    help="paged engine: tokens per KV page "
                         "(cache.page_size)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="paged engine: physical page count "
                         "(cache.num_pages; default matches the slot "
                         "pool's worst-case capacity)")
    ap.add_argument("--speculative", action="store_true",
                    help="draft-model speculative decoding on the paged "
                         "pool (engine.name=speculative; needs a draft "
                         "source: --draft-layers or --draft-arch)")
    ap.add_argument("--draft-layers", type=int, default=None,
                    metavar="N",
                    help="truncated-layer draft: reuse the target's "
                         "first N layers (draft.num_layers)")
    ap.add_argument("--draft-arch", default=None, metavar="ARCH",
                    help="independent draft model from the configs "
                         "registry, same vocab (draft.arch)")
    ap.add_argument("--gamma", type=int, default=None,
                    help="speculative lookahead tokens per draft window "
                         "(draft.gamma)")
    ap.add_argument("--stream", nargs="?", const="", default=None,
                    metavar="JSONL",
                    help="stream every emitted token through the "
                         "on_token hook (stream.enabled); with a path, "
                         "also write the JSONL sink (stream.path)")
    ap.add_argument("--sample", action="store_true",
                    help="seeded stochastic sampling instead of greedy "
                         "(sampling.method=sample; keyed by request id + "
                         "token index, reproducible)")
    ap.add_argument("--temperature", type=float, default=None)
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--top-p", type=float, default=None)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--budget", type=int, default=None,
                    help="continuous runtime: per-step decode token budget")
    ap.add_argument("--policy", default=None, choices=["fifo", "ljf"],
                    help="admission order (registered scheduler policy)")
    ap.add_argument("--verify", type=int, default=None,
                    help="check N outputs against single-request decoding "
                         "(-1 = all)")
    ap.add_argument("--checkpoint", default=None, metavar="PARAMS_NPZ",
                    help="serve params from a training-run artifact "
                         "(ExperimentSpec execution.checkpoint)")
    ap.add_argument("--seed", type=int, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.config:
        spec = api.load_any_spec(args.config)
        if not isinstance(spec, api.ServeSpec):
            raise SystemExit(f"{args.config} is a {spec.kind!r} spec; "
                             f"the serve CLI needs kind 'serve' "
                             f"(use repro.launch.train for experiments)")
    else:
        spec = default_serve_spec()
    spec = api.apply_overrides(spec, _legacy_overrides(args) + args.sets)
    if args.print_spec:
        print(spec.to_json())
        return

    report = api.run(spec)
    print(f"arch={report.arch} " + report.summary())
    for r in report.per_request[:3]:
        print(f"  req {r['rid']}: {r['tokens'][:12]}...")
    if report.verified is not None:
        print(f"verified token-identical: {report.verified['checked']} "
              f"requests")


if __name__ == "__main__":
    main()
