"""Milliseconds a traced call of `fluid.ProgramDecoder` for which the
first device idled between two programs while the host was inside the
call: each such gap is cut at the edges of the program's `decode/*`
spans (`decode/call` > `decode/prep`, `decode/dispatch`, `decode/fetch`)
and goes to the innermost one open (benchmark/reduce/decoder_trace.py,
as `executor_idle_ms_per_step` does for the executor's spans).  Under
`decode/prep` the device waits while the host hands over the state;
`prep` returns when the transfers are enqueued and `decode/dispatch`
when the program is (a few milliseconds), so what of the transfer is
still in flight the device waits for under `decode/fetch`, before the
call's program starts; after it, under `decode/fetch`, only the
program's own end.  Under the profiler a traced call whose program was
loaded from the compile cache starts about a second later still (seen
on the chip, PERF.md section 6): that part no untraced call pays.

Prints the split by span, what stays with the benchmark's own
`bench/generate` outside the call ("bench/generate (no program span)":
with the split it adds up to the breakdown's `bench/generate` entry),
the part of it before the call's first program starts, and each span's
self time on the host."""

from benchmark.reduce import decoder_trace, program_spans

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    found = decoder_trace.traced(run)
    if found is None:
        return None
    trace, spans, calls = found
    idle = decoder_trace.idle_by_span(trace, spans, min(trace.devices))
    own = program_spans.self_seconds(spans)
    n = len(calls)
    before = sum(decoder_trace.idle_before_program(
        trace, call, min(trace.devices)) for call in calls)
    print("device idle between programs, ms a call of %d, by the innermost "
          "span open: %s; %.3f of it before the call's first program "
          "starts; self time on the host, ms a call: %s"
          % (n, ", ".join("%s %.3f" % (name, s / n * 1e3)
                          for name, s in idle.most_common() if s),
             before / n * 1e3,
             ", ".join("%s %.3f" % (name, s / n * 1e3)
                       for name, s in own.most_common())), flush=True)
    return sum(s for name, s in idle.items()
               if name.startswith(decoder_trace.PREFIX)) / n * 1e3
