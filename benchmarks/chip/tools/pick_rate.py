"""Set an open-loop mix's rate from a sweep: 0.8 x the highest swept rate
that answered every request and drained within ``drain_ok`` seconds of
the window's close.

  python3 benchmarks/chip/tools/pick_rate.py <sweep output> \
      <traffic/<mix>.json> <drain_ok seconds>

Reads the JSON lines that ``tools/sweep.py`` prints and rewrites the mix's
``rate_per_s``; exits 1 when no swept rate kept up.
"""
import json
import sys


def main(sweep: str, mix: str, drain_ok: float) -> int:
    rows = [json.loads(ln) for ln in open(sweep) if ln.startswith("{")]
    kept = [r["rate_per_s"] for r in rows
            if r["answered"] == r["offered"]
            and r["drain_after_close_s"] <= drain_ok]
    if not kept:
        print("no swept rate kept up")
        return 1
    knee = max(kept)
    rate = round(0.8 * knee, 1)
    with open(mix) as f:
        traffic = json.load(f)
    traffic["rate_per_s"] = rate
    with open(mix, "w") as f:
        f.write(json.dumps(traffic, indent=2) + "\n")
    print(f"knee {knee} rate {rate}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2], float(sys.argv[3])))
