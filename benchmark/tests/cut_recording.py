"""Cut a chip recording down to what a test needs: device 0's operations
under the given op types during one step (from the first run of the
step's first such instruction to its next run), each with its `tf_op`
path, and one `bench/window` span over them.  How the recordings under
benchmark/tests/data/ were made:

    python3 benchmark/tests/cut_recording.py <trace dir> <out.xplane.pb> \
        conv2d conv2d_grad [--match <regex on the path>]

Prints the microseconds kept by (op type, instance component) as they
were summed here, for the test's docstring.
"""

import collections
import re
import sys


def cut(trace_dir, out_path, op_types, match=""):
    from jax.profiler import ProfileData

    from benchmark.reduce import op_scopes, xplane

    path = xplane.find_xplane(trace_dir)
    paths = op_scopes.metadata_stat(path, "/device:TPU:0", "tf_op")
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == xplane.OPS_LINE:
                events = [(ev.start_ns, ev.duration_ns, ev.name)
                          for ev in line.events
                          if op_scopes.op_type(paths.get(ev.name, ""))
                          in op_types
                          and re.search(match, paths[ev.name])]
    events.sort()
    middle = events[len(events) // 2][2]
    starts = [i for i, ev in enumerate(events) if ev[2] == middle]
    kept = events[starts[0]:starts[1]]
    origin = kept[0][0]
    ids, lines, metadata = {}, [], []
    sums = collections.Counter()
    for start, length, text in kept:
        if text not in ids:
            ids[text] = len(ids) + 1
            metadata.append(
                'event_metadata { key: %d value { id: %d name: "%s" stats '
                '{ metadata_id: 9 str_value: "%s" } } }'
                % (ids[text], ids[text],
                   text.replace("\\", "\\\\").replace('"', '\\"'),
                   paths[text]))
        lines.append("events { metadata_id: %d offset_ps: %d duration_ps: "
                     "%d }" % (ids[text], round((start - origin) * 1000),
                               round(length * 1000)))
        parts = op_scopes.components(paths[text])
        sums[parts[1], parts[2]] += length
    end = max(s + d for s, d, _ in kept) - origin
    text = """
planes {
  name: "/device:TPU:0"
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
""" % ("\n    ".join(lines), "\n  ".join(metadata), round(end * 1000) + 1000)
    with open(out_path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    for key, ns in sorted(sums.items()):
        print("%-16s %-40s %10.3f us" % (key + (ns * 1e-3,)))
    print("%d events of %d instructions, %.3f us from the first to the "
          "end of the last" % (len(kept), len(ids), end * 1e-3))


if __name__ == "__main__":
    sys.path.insert(0, ".")
    args = sys.argv[1:]
    pattern = ""
    if "--match" in args:
        pattern = args[args.index("--match") + 1]
        del args[args.index("--match"):args.index("--match") + 2]
    cut(args[0], args[1], set(args[2:]), pattern)
