"""Trim a profiler trace to what ``trace_reduce`` reads, so that a
recorded chip trace can be kept as a small test fixture:

    python benchmarks/chip/tests/trim_trace.py <in.xplane.pb> <out.xplane.pb>

Kept: every ``/device:TPU:<n>`` plane's ``XLA Ops`` and ``XLA Modules``
events, each named by its HLO instruction alone (``%fusion.12 = ...``
loses the text after ``=``), and the benchmark's host spans.  Times are
copied to the picosecond.
"""
import sys

from jax.profiler import ProfileData

SPAN_PREFIXES = ("rollout.", "gateway.", "bench.")


def _quote(s: str) -> str:
    return '"' + s.replace("\\\\", "\\\\\\\\").replace('"', '\\\\"') + '"'


def trim(src: str) -> bytes:
    pd = ProfileData.from_file(src)
    planes = []
    for pid, plane in enumerate(pd.planes, 1):
        device = plane.name.startswith("/device:TPU:")
        if not (device or plane.name.startswith("/host:")):
            continue
        names, lines = {}, []
        for lid, line in enumerate(plane.lines, 1):
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            evs = []
            for ev in line.events:
                name = ev.name
                if device:
                    name = name.split(" = ", 1)[0] + " = "
                elif not name.startswith(SPAN_PREFIXES):
                    continue
                mid = names.setdefault(name, len(names) + 1)
                evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                           f"{round(ev.start_ns * 1000)} duration_ps: "
                           f"{round(ev.duration_ns * 1000)} }}")
            if evs:
                lines.append(f"lines {{ id: {lid} name: {_quote(line.name)}"
                             f" timestamp_ns: 0 {' '.join(evs)} }}")
        if lines:
            meta = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                            f"name: {_quote(n)} }} }}"
                            for n, i in names.items())
            planes.append(f"planes {{ id: {pid} name: {_quote(plane.name)} "
                          f"{' '.join(lines)} {meta} }}")
    return ProfileData.text_proto_to_serialized_xspace("\n".join(planes))


if __name__ == "__main__":
    with open(sys.argv[2], "wb") as f:
        f.write(trim(sys.argv[1]))
