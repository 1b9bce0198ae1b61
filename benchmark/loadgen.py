"""The benchmark's load generator: arrival schedules, percentiles and an
open-loop HTTP sender that runs as a process of its own.

The schedule builder, the accounting from the scheduled send time and
`percentile` are copies of `paddle_tpu/obs/load.py` (`build_schedule`,
`run_open_loop`, `percentile`), kept here so that a change to the
program cannot change the yardstick.  Two things differ on purpose.  The
sender is a child process that never imports JAX, so its threads do not
share the server's interpreter lock; and it sends bodies that were
encoded during set-up, so no JSON is encoded on the timed path.

As a program:  python3 loadgen.py <plan.json>
reads the plan (target, body files, schedule, number of sender threads),
loads the bodies, connects, prints "ready", waits for one line on its
standard input, offers the schedule, writes its report to the plan's
`report` path and exits.  This file imports nothing but the standard
library.
"""

import http.client
import json
import math
import random
import sys
import threading
import time

# what a request that never got an HTTP answer is counted as (connection
# refused or reset, timeout): numeric, beside the real statuses
CLIENT_ERROR_STATUS = 599


def sample_size(mix, rng):
    """One request size from `mix`, a {size: weight} mapping."""
    sizes = sorted(mix, key=int)
    x = rng.random() * sum(float(mix[s]) for s in sizes)
    acc = 0.0
    for s in sizes:
        acc += float(mix[s])
        if x <= acc:
            return int(s)
    return int(sizes[-1])


def rate_at(t, rate, bursts=None):
    """The offered rate at offset `t`: `rate`, times `bursts["factor"]`
    during the first `bursts["length_s"]` of every `bursts["every_s"]`."""
    if bursts and (t % float(bursts["every_s"])) < float(bursts["length_s"]):
        return float(rate) * float(bursts["factor"])
    return float(rate)


def build_schedule(rate, duration_s, mix, seed, arrival="poisson",
                   bursts=None):
    """The open-loop schedule: [(offset_s, size)], fixed before the run,
    so that it never reacts to the server.  "poisson" draws exponential
    gaps at the (burst-modulated) rate, "uniform" spaces them evenly.
    Every request is due inside [0, duration_s)."""
    if arrival not in ("poisson", "uniform"):
        raise ValueError("arrival must be poisson or uniform: %r" % arrival)
    rng = random.Random(seed)
    schedule = []
    t = 0.0
    while t < float(duration_s):
        schedule.append((t, sample_size(mix, rng)))
        r = rate_at(t, rate, bursts)
        if r <= 0:
            raise ValueError("offered rate fell to %r at t=%.3fs" % (r, t))
        t += rng.expovariate(r) if arrival == "poisson" else 1.0 / r
    return schedule


def percentile(sorted_vals, p):
    """Nearest-rank percentile of an ascending list (p in (0, 100]);
    None when it is empty."""
    if not sorted_vals:
        return None
    rank = max(1, int(math.ceil(p / 100.0 * len(sorted_vals))))
    return sorted_vals[rank - 1]


def offer(plan, bodies, go):
    """Send the plan's schedule open loop and return one record per
    request.  `go()` blocks until the parent says start; its return is
    the first instant of the window.  A pool of sender threads takes the
    arrivals in order and sleeps until each is due; a request that finds
    every sender busy goes out late, and its lateness counts against the
    server in `latency` and is reported as `late`."""
    schedule = plan["schedule"]
    keep = set(plan.get("keep", ()))
    records = [None] * len(schedule)
    cursor = [0]
    lock = threading.Lock()
    headers = {"Content-Type": "application/json"}
    conns = [http.client.HTTPConnection(plan["host"], plan["port"],
                                        timeout=plan["timeout_s"])
             for _ in range(plan["senders"])]
    for conn in conns:
        conn.connect()
    t0 = go()

    def sender(conn):
        while True:
            with lock:
                i = cursor[0]
                if i >= len(schedule):
                    return
                cursor[0] = i + 1
            offset, body = schedule[i]
            due = t0 + offset
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            sent = time.perf_counter()
            answer = None
            try:
                conn.request("POST", plan["path"], bodies[body], headers)
                resp = conn.getresponse()
                answer = resp.read()
                status = resp.status
            except (OSError, http.client.HTTPException):
                status = CLIENT_ERROR_STATUS
                conn.close()    # the next request reconnects
            done = time.perf_counter()
            records[i] = {"due": due, "sent": sent, "done": done,
                          "status": status, "body": body}
            if i in keep and answer is not None and status == 200:
                records[i]["answer"] = answer.decode("utf-8", "replace")

    threads = [threading.Thread(target=sender, args=(c,), daemon=True)
               for c in conns]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for conn in conns:
        conn.close()
    return {"t0": t0, "end": time.perf_counter(), "records": records}


def main(argv):
    with open(argv[1]) as f:
        plan = json.load(f)
    bodies = []
    for path in plan["bodies"]:
        with open(path, "rb") as f:
            bodies.append(f.read())

    def go():
        print("ready", flush=True)
        sys.stdin.readline()
        return time.perf_counter()

    report = offer(plan, bodies, go)
    with open(plan["report"], "w") as f:
        json.dump(report, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
