"""How ``caffenet-train.scoped.xplane.pb.gz`` was cut from a trace recorded on
the chip with the round program's scopes in it (PR 24;
``python make_trimmed_scoped_trace.py <in.xplane.pb> <out.pb.gz> [rounds]``).

As ``make_trimmed_trace.py`` cuts, and besides: the first chip only; of the
host the harness's marks and the program's own spans (``obs.span`` opens a
``TraceAnnotation`` since PR 24); of the device's events those of ``rounds``
rounds from the middle of the trace, so that whole executions of the round
program lie inside the window; and each operation keeps its ``op_name`` where
the profiler keeps it, in the stat ``tf_op`` of its event metadata.  Times
are untouched."""

import gzip
import sys

from jax.profiler import ProfileData
from make_trimmed_trace import LINES, MARKS, trimmed_name  # beside this file

from benchmark import scopes, xplane

SPANS = ("average", "execute", "assemble", "h2d")
CHIP = "/device:TPU:0"


def quoted(text):
    return '"' + text.replace("\\", "\\\\").replace('"', '\\"') + '"'


def main(src, dst, rounds=3):
    data = ProfileData.from_file(src)
    op_names = scopes.op_names(src).get(0, {})
    host = next(p for p in data.planes if p.name == xplane.HOST_PLANE)
    feeds = sorted(
        ev.start_ns for line in host.lines for ev in line.events
        if ev.name == MARKS[0]
    )
    k = len(feeds) // 2
    host_t0, t0, t1 = feeds[k - 2], feeds[k], feeds[k + rounds]
    planes = []
    for plane in data.planes:
        if plane.name not in (CHIP, xplane.HOST_PLANE):
            continue
        on_device = plane.name == CHIP
        names, stacks, lines = {}, {}, []
        for line in plane.lines:
            if on_device and line.name not in LINES:
                continue
            events = []
            for ev in line.events:
                if on_device:
                    if ev.start_ns + ev.duration_ns < t0 or ev.start_ns > t1:
                        continue
                elif (ev.name not in MARKS + SPANS
                      or not host_t0 <= ev.start_ns < t1):
                    continue
                name = trimmed_name(line.name, ev.name) if on_device else ev.name
                key = names.setdefault(name, len(names) + 1)
                if on_device and ev.name in op_names:
                    stacks.setdefault(key, op_names[ev.name])
                events.append(
                    f"events {{ metadata_id: {key} offset_ps: "
                    f"{int(round(ev.start_ns * 1000))} duration_ps: "
                    f"{int(round(ev.duration_ns * 1000))} }}"
                )
            if events:
                lines.append(
                    f"lines {{ id: {len(lines) + 1} name: {quoted(line.name)} "
                    f"timestamp_ns: 0\n" + "\n".join(events) + "\n}"
                )
        meta = "\n".join(
            f"event_metadata {{ key: {k} value {{ id: {k} name: {quoted(n)}"
            + (f" stats {{ metadata_id: 1 str_value: {quoted(stacks[k] + ':')} }}"
               if k in stacks else "") + " } }"
            for n, k in names.items()
        )
        stat = ('stat_metadata { key: 1 value { id: 1 name: '
                f'{quoted(scopes.OP_NAME_STAT)} }} }}') if on_device else ""
        planes.append(
            f"planes {{ id: {len(planes) + 1} name: {quoted(plane.name)}\n"
            + "\n".join(lines) + "\n" + meta + "\n" + stat + "\n}"
        )
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(planes))
    with gzip.open(dst, "wb") as f:
        f.write(blob)
    print(f"{dst}: {len(blob)} bytes before gzip, window {t0}..{t1} ns")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *map(int, sys.argv[3:4]))
