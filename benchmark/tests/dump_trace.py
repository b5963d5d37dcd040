"""Look at one capture by hand: ``python3 benchmark/tests/dump_trace.py
<trace_dir> [out.txt]`` prints every plane and line, and for each device
plane's ``XLA Ops`` line the instructions by device time with one event's
stats — how kernels and collectives are named on this installation."""

import collections
import glob
import os
import sys


def main():
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(sys.argv[1], "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    out = open(sys.argv[2], "w") if len(sys.argv) > 2 else sys.stdout
    print(paths[-1], os.path.getsize(paths[-1]), "bytes", file=out)
    for plane in ProfileData.from_file(paths[-1]).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: "
              + ", ".join(f"{ln.name}({len(list(ln.events))})"
                          for ln in lines), file=out)
        for ln in lines:
            if not plane.name.startswith("/device:"):
                names = collections.Counter(
                    ev.name for ev in ln.events
                    if ev.name.startswith("bench/"))
                if names:
                    print(f"  line {ln.name!r}: {dict(names)}", file=out)
                continue
            total = collections.defaultdict(float)
            sample = {}
            for ev in ln.events:
                total[ev.name] += ev.duration_ns
                sample.setdefault(ev.name, ev)
            print(f"  line {ln.name!r}: {len(total)} names", file=out)
            for name, ns in sorted(total.items(), key=lambda kv: -kv[1])[:40]:
                ev = sample[name]
                stats = {k: (str(v)[:80]) for k, v in ev.stats}
                print(f"    {ns / 1e6:10.3f} ms  {name[:100]}  {stats}",
                      file=out)


if __name__ == "__main__":
    main()
