"""Milliseconds a call of `fluid.ProgramDecoder` spends before its
program is dispatched: validation and the way of the caller's
`init_state` and prompt to the device (`_prep`, `_norm_prompt`), the
`decode/prep` span's interval.  The program's own counters,
`decoder_seconds_total{phase=prep}` over `decoder_calls_total`, over
every call of the process (the warm-up's, the untraced window's, the
traced one and the calls after it), so it needs no profiler and one name
serves the four generation cells; the state a call hands over is the
same at every call of a cell.  The transfer is enqueued, not waited for:
what of it is still in flight when the span closes the device waits for
under `decode/fetch`, before the call's program starts
(`decoder_idle_ms_per_call`): this is the host's half of the state's way
to the device.

Prints the calls, the GB of state a call was handed by where it was
(`decoder_state_bytes_total{source}`), the rate that makes, and the
`dispatch` and `fetch` seconds a call beside it (the first call's
dispatch holds the trace and the compile or the cache's load)."""

from benchmark.reduce import decoder_trace

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "program_counter"


def read(run):
    found = decoder_trace.counters()
    calls = sum(decoder_trace.labelled(found, "decoder_calls_total",
                                       "mode").values())
    seconds = decoder_trace.labelled(found, "decoder_seconds_total", "phase")
    if run.peaks is None or not calls or "prep" not in seconds:
        return None
    handed = decoder_trace.labelled(found, "decoder_state_bytes_total",
                                    "source")
    each = {source: handed.get(source, 0) / calls / 1e9
            for source in ("host", "device")}
    prep = seconds["prep"] / calls
    print("decoder: %d calls, %d programs built; a call was handed %.3f GB "
          "of state from the host and %.3f GB as device arrays, %.2f GB/s "
          "through prep; seconds a call: prep %.4f, dispatch %.4f, fetch "
          "%.4f"
          % (calls, sum(decoder_trace.labelled(
              found, "decoder_programs_total", "mode").values()),
             each["host"], each["device"], sum(each.values()) / prep,
             prep, seconds.get("dispatch", 0.0) / calls,
             seconds.get("fetch", 0.0) / calls), flush=True)
    return prep * 1e3
