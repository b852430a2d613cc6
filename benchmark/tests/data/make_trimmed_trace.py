"""How ``caffenet-dp4.trimmed.xplane.pb.gz`` was cut from a trace recorded on
the chip (``python make_trimmed_trace.py <in.xplane.pb> <out.pb.gz> <devices>``).

Kept: of the first ``devices`` chips the lines the reduction reads, of the host
every ``TraceAnnotation`` of the harness; of the marks those of four rounds
from the middle of the trace, and of the device's events those of the last of
the four, where the window's rule (skip two marks) opens it, so that one whole
execution of the round program and its collective is there.  Each operation's HLO line is cut down to what the
reduction parses: ``%name = type opcode(...)``.  Times are untouched."""

import gzip
import sys

from jax.profiler import ProfileData

from benchmark import xplane

MARKS = ("feed.next_round", "trainer.round", "wait.losses", "drain")
LINES = (xplane.OPS_LINE, xplane.MODULES_LINE, xplane.ASYNC_LINE)


def trimmed_name(line, name):
    if line == xplane.MODULES_LINE or not xplane.HLO.match(name):
        return name
    op, shape = xplane.short_name(name).partition(" ")[::2]
    return f"%{op} = {shape or 'token[]'} {xplane.opcode(name)}(...)"


def main(src, dst, devices):
    data = ProfileData.from_file(src)
    host = next(p for p in data.planes if p.name == xplane.HOST_PLANE)
    feeds = sorted(
        ev.start_ns for line in host.lines for ev in line.events
        if ev.name == MARKS[0]
    )
    k = len(feeds) // 2
    host_t0, t0, t1 = feeds[k - 2], feeds[k], feeds[k + 1]
    planes = []
    for plane in data.planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if not (m and int(m.group(1)) < devices) and plane.name != xplane.HOST_PLANE:
            continue
        names, lines = {}, []
        for line in plane.lines:
            on_device = plane.name != xplane.HOST_PLANE
            if on_device and line.name not in LINES:
                continue
            events = []
            for ev in line.events:
                if on_device:
                    if ev.start_ns + ev.duration_ns < t0 or ev.start_ns > t1:
                        continue
                elif ev.name not in MARKS or not host_t0 <= ev.start_ns < t1:
                    continue
                name = trimmed_name(line.name, ev.name) if on_device else ev.name
                key = names.setdefault(name, len(names) + 1)
                events.append(
                    f"events {{ metadata_id: {key} offset_ps: "
                    f"{int(round(ev.start_ns * 1000))} duration_ps: "
                    f"{int(round(ev.duration_ns * 1000))} }}"
                )
            if events:
                lines.append(
                    f'lines {{ id: {len(lines) + 1} name: "{line.name}" '
                    f"timestamp_ns: 0\n" + "\n".join(events) + "\n}"
                )
        meta = "\n".join(
            f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
            for n, k in names.items()
        )
        planes.append(
            f'planes {{ id: {len(planes) + 1} name: "{plane.name}"\n'
            + "\n".join(lines) + "\n" + meta + "\n}"
        )
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(planes))
    with gzip.open(dst, "wb") as f:
        f.write(blob)
    print(f"{dst}: {len(blob)} bytes before gzip, window {t0}..{t1} ns")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]))
