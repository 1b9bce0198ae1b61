"""Milliseconds the session's caches take from the host to the device:
the keys, the values and the chooser's keys of every row (what a
decode-pool chip receives from the prefill pool), put on the device once
in set-up and waited for; every call is then handed those device arrays,
which `ProgramDecoder` takes where they lie.  So it is inside `setup_s`
and inside no timed call: a call of the sibling cells
(`session_restore_ms_per_call`, `long_restore_ms_per_call`) pays it
every time, which at this cell's 5.7 GB was 4.2-5.9 s of an 11 s call
by how busy the shared host was (PERF.md section 6, PR 58).  Host
clock."""

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    if run.peaks is None:
        return None
    return run.facts.get("sparse_restore_ms")
