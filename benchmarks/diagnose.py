"""HLO collective diagnostics for one dry-run cell.

  PYTHONPATH=src python -m benchmarks.diagnose --arch qwen2_1_5b \\
      --shape decode_32k [--shard-acts] [--embed-dshard] [--top 15]

Prints the top-N collectives by result bytes with their HLO lines — the
"profile" of the dry-run methodology (no real hardware): every hillclimb
hypothesis starts from this list.
"""
import os

# the collective dry-run needs a big fake device mesh
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))

# ruff: noqa: E402
import argparse
import re

COLL_RE = re.compile(
    r"(?:ROOT )?%?([\w\.\-]+) = (.*?) (all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)(-start)?\(")


def top_collectives(hlo: str, n=15):
    from repro.launch.dryrun import _shape_bytes

    rows = []
    for line in hlo.splitlines():
        ls = line.strip()
        m = COLL_RE.match(ls)
        if not m or "-done(" in ls:
            continue
        rows.append((_shape_bytes(m.group(2)), m.group(3), ls[:240]))
    rows.sort(reverse=True)
    return rows[:n]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--shard-acts", action="store_true")
    ap.add_argument("--embed-dshard", action="store_true")
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--quant", default="w8a8")
    args = ap.parse_args()

    if not args.arch or not args.shape:
        ap.error("--arch and --shape are required")

    from repro.launch.dryrun import arch_config, collective_bytes, lower_cell
    from repro.launch.mesh import make_production_mesh

    cfg = arch_config(args.arch, args.shape, args.quant,
                      shard_acts=args.shard_acts)
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    kw = {}
    if args.embed_dshard:
        kw = {"fsdp_exclude": ("embed", "lm_head")}
    lowered, _ = lower_cell(cfg, args.shape, mesh,
                            microbatches=args.microbatches, **kw)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    cb = collective_bytes(hlo)
    print(f"total collective result bytes: {cb['total_bytes'] / 1e9:.2f} GB")
    print(f"by type: "
          f"{ {k: round(v / 1e9, 2) for k, v in cb['bytes'].items() if v} }")
    print(f"counts : { {k: v for k, v in cb['counts'].items() if v} }\n")
    for size, op, line in top_collectives(hlo, args.top):
        print(f"{size / 1e9:8.3f} GB  {op:18s} {line[:200]}")
    ca = compiled.cost_analysis() or {}
    print(f"\nflops={ca.get('flops', 0):.4g}  "
          f"bytes={ca.get('bytes accessed', 0):.4g}")
    ma = compiled.memory_analysis()
    if ma:
        print(f"temp={getattr(ma, 'temp_size_in_bytes', 0) / 1e9:.2f} GB  "
              f"args={getattr(ma, 'argument_size_in_bytes', 0) / 1e9:.2f} GB")


if __name__ == "__main__":
    main()
