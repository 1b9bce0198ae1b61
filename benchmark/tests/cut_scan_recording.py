"""Cut a chip recording of a traced generation call down to what a test
of the scans' readers needs: of device 0, one step of the prefill scan
and one step of the decoding scan (each the middle one: from one run of
an instruction that runs once a step to its next run), every operation
of those steps with its `tf_op` path, each scan's `while` cut to the
step kept, and one `bench/window` span over them.  The decoding step is
moved to a microsecond after the prefill step (seconds lie between them
on the chip); an instruction's text is cut after its opcode (the name,
the shape, the opcode and a fusion's kind are what the reducers read:
benchmark/reduce/xplane.py `parse_instruction`).  What cut_recording.py
is for a training step, whose paths begin with the op type: under the
decoder's scans a path begins `jit(<lambda>)/while/body/closed_call`.
How `data/exaone-turn-32k-ep16-steps.xplane.pb` was made:

    python3 benchmark/run.py --workload exaone-turn-32k-ep16 --seed <n> \
        --seconds 20 --trace 1
    python3 benchmark/tests/cut_scan_recording.py \
        .bench_work/exaone-turn-32k-ep16/trace steps.json   # on the chip's host
    python3 benchmark/tests/cut_scan_recording.py steps.json out.xplane.pb

The first form prints which step of how many it kept of either scan: the
facts a test gives the readers say one step at that position.
"""

import collections
import json
import re
import sys

PLANE = "/device:TPU:0"
GAP_NS = 1000


def _short(text):
    """An instruction's text up to its opcode (a shape of more than 48
    characters cut there: a scan's `while` spells out everything it
    carries), with a fusion's kind."""
    from benchmark.reduce import xplane

    match = xplane.INSTRUCTION.match(text)
    if not match:
        return text
    name, opcode = match.group("name"), match.group("opcode")
    shape = match.group(0)[len("%%%s = " % name):-len(" %s(" % opcode)]
    if len(shape) > 48:
        shape = shape[:48].split(" ")[0] + "..."
    kind = xplane.FUSION_KIND.search(text)
    return "%%%s = %s %s(...)%s" % (name, shape, opcode,
                                   ", " + kind.group(0) if kind else "")


def _step(events, scan):
    """(the events of the middle step of `scan` = (start, end, text),
    the step's (start, end), its index, the steps): a step runs from one
    run of the scan's first once-a-step instruction to its next run."""
    lo, hi, _ = scan
    inside = [ev for ev in events
              if lo <= ev[0] and ev[0] + ev[1] <= hi and (ev[0], ev[0] + ev[1]) != (lo, hi)]
    counts = collections.Counter(ev[2] for ev in inside)
    # most instructions of a scan's body run once a step (the body of a
    # `while` inside it more often)
    steps = collections.Counter(counts.values()).most_common(1)[0][0]
    marker = next(ev[2] for ev in inside if counts[ev[2]] == steps)
    starts = [ev[0] for ev in inside if ev[2] == marker]
    at = steps // 2
    first, last = starts[at], starts[at + 1]
    return ([ev for ev in inside if first <= ev[0] < last], (first, last),
            at, steps)


def dump(trace_dir, out_path):
    from jax.profiler import ProfileData

    from benchmark.reduce import op_scopes, xplane

    path = xplane.find_xplane(trace_dir)
    paths = op_scopes.metadata_stat(path, PLANE, "tf_op")
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != PLANE:
            continue
        for line in plane.lines:
            if line.name == xplane.OPS_LINE:
                events = sorted((ev.start_ns, ev.duration_ns, ev.name)
                                for ev in line.events)
    whiles = []     # the outermost, as benchmark/reduce/scans.py has them
    for start, length, text in events:
        if xplane.parse_instruction(text)[1] != "while" \
                or (whiles and start + length <= whiles[-1][1]):
            continue
        whiles.append((start, start + length, text))
    scans = sorted(sorted(whiles, key=lambda w: w[0] - w[1])[:2])
    out = {"scans": []}
    for name, scan in zip(("prefill", "decoding"), scans):
        kept, (first, last), at, steps = _step(events, scan)
        print("%s scan: %d steps over %.3f ms, step %d kept: %d operations, "
              "%.3f us" % (name, steps, (scan[1] - scan[0]) * 1e-6, at,
                           len(kept), (last - first) * 1e-3))
        out["scans"].append({
            "name": name, "steps": steps, "kept": at,
            "while": _short(scan[2]), "length_ns": last - first,
            "events": [[start - first, length, _short(text),
                        paths.get(text, "")]
                       for start, length, text in kept]})
    with open(out_path, "w") as f:
        json.dump(out, f)


def write(json_path, out_path):
    from jax.profiler import ProfileData

    with open(json_path) as f:
        cut = json.load(f)
    ids, lines, metadata = {}, [], []

    def event(text, path, offset_ns, length_ns):
        key = (text, path)
        if key not in ids:
            ids[key] = len(ids) + 1
            quoted = [s.replace("\\", "\\\\").replace('"', '\\"')
                      for s in key]
            metadata.append(
                'event_metadata { key: %d value { id: %d name: "%s" stats '
                '{ metadata_id: 9 str_value: "%s" } } }'
                % (ids[key], ids[key], quoted[0], quoted[1]))
        lines.append("events { metadata_id: %d offset_ps: %d duration_ps: "
                     "%d }" % (ids[key], offset_ns * 1000, length_ns * 1000))

    origin = 0
    for scan in cut["scans"]:
        event(_short(scan["while"]), "", origin, scan["length_ns"])
        for start, length, text, path in scan["events"]:
            event(_short(text), path, origin + start, length)
        origin += scan["length_ns"] + GAP_NS
    text = """
planes {
  name: "%s"
  lines { name: "XLA Ops" timestamp_ns: 0
    %s
  }
  %s
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: %d }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
}
""" % (PLANE, "\n    ".join(lines), "\n  ".join(metadata), origin * 1000)
    with open(out_path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    print("%d events of %d instructions, %.3f us in all"
          % (len(lines), len(ids), origin * 1e-3))


if __name__ == "__main__":
    sys.path.insert(0, ".")
    source, out = sys.argv[1:]
    (write if re.search(r"\.json$", source) else dump)(source, out)
