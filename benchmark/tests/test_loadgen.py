"""The load generator's arithmetic on fixed seeds, and its sender against
a server of the test's own."""

import http.server
import json
import subprocess
import sys
import threading

import pytest

from benchmark import loadgen


@pytest.mark.parametrize("values,p,expected", [
    ([], 50, None),
    ([7.0], 50, 7.0),
    ([7.0], 95, 7.0),
    ([1, 2, 3, 4], 50, 2),           # rank ceil(0.5 * 4) = 2
    ([1, 2, 3, 4], 75, 3),
    ([1, 2, 3, 4], 76, 4),           # rank ceil(3.04) = 4
    (list(range(1, 101)), 95, 95),
    (list(range(1, 101)), 100, 100),
    (list(range(1, 21)), 95, 19),    # rank ceil(19.0) = 19
])
def test_percentile_is_nearest_rank(values, p, expected):
    assert loadgen.percentile(values, p) == expected


def test_schedule_uniform_is_evenly_spaced_inside_the_window():
    got = loadgen.build_schedule(4.0, 1.0, {"1": 1}, seed=0,
                                 arrival="uniform")
    assert got == [(0.0, 1), (0.25, 1), (0.5, 1), (0.75, 1)]


def test_schedule_bursts_multiply_the_rate_at_the_start_of_each_period():
    got = loadgen.build_schedule(
        2.0, 4.0, {"1": 1}, seed=0, arrival="uniform",
        bursts={"every_s": 2.0, "length_s": 0.5, "factor": 4})
    assert [t for t, _ in got] == pytest.approx(
        [0.0, 0.125, 0.25, 0.375, 0.5, 1.0, 1.5,
         2.0, 2.125, 2.25, 2.375, 2.5, 3.0, 3.5])


def test_schedule_poisson_is_fixed_by_its_seed():
    mix = {"1": 0.85, "2": 0.10, "4": 0.05}
    a = loadgen.build_schedule(10.0, 2.0, mix, seed=7)
    assert a == loadgen.build_schedule(10.0, 2.0, mix, seed=7)
    assert a != loadgen.build_schedule(10.0, 2.0, mix, seed=8)
    assert len(a) == 29
    assert [round(t, 6) for t, _ in a[:4]] == \
        [0.0, 0.016352, 0.023871, 0.069393]
    assert all(0 <= t < 2.0 for t, _ in a)
    assert {n for _, n in a} <= {1, 2, 4}


def test_schedule_mix_shares_over_many_draws():
    mix = {"1": 0.85, "2": 0.10, "4": 0.05}
    sizes = [n for _, n in loadgen.build_schedule(1000.0, 20.0, mix, 3)]
    assert len(sizes) == pytest.approx(20000, rel=0.03)
    for size, share in ((1, 0.85), (2, 0.10), (4, 0.05)):
        assert sizes.count(size) / len(sizes) == pytest.approx(share,
                                                               abs=0.01)


def test_schedule_refuses_an_unknown_arrival():
    with pytest.raises(ValueError):
        loadgen.build_schedule(1.0, 1.0, {"1": 1}, 0, arrival="bursty")


class _Echo(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *args):
        pass

    def do_POST(self):
        body = self.rfile.read(int(self.headers["Content-Length"]))
        status = 500 if body == b"fail" else 200
        self.send_response(status)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def test_child_process_offers_the_plan_and_reports_every_request(tmp_path):
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Echo)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        bodies = []
        for i, text in enumerate(("zero", "fail")):
            path = tmp_path / ("%d.json" % i)
            path.write_text(text)
            bodies.append(str(path))
        plan = {"host": "127.0.0.1", "port": server.server_address[1],
                "path": "/", "bodies": bodies, "senders": 2,
                "timeout_s": 10, "keep": [0],
                "schedule": [[0.0, 0], [0.05, 1], [0.1, 0]],
                "report": str(tmp_path / "report.json")}
        (tmp_path / "plan.json").write_text(json.dumps(plan))
        child = subprocess.Popen(
            [sys.executable, loadgen.__file__, str(tmp_path / "plan.json")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        assert child.stdout.readline().strip() == "ready"
        child.stdin.write("go\n")
        child.stdin.flush()
        assert child.wait(timeout=30) == 0
    finally:
        server.shutdown()
        thread.join(timeout=10)
        server.server_close()
    assert not thread.is_alive()
    report = json.loads((tmp_path / "report.json").read_text())
    records = report["records"]
    assert [r["status"] for r in records] == [200, 500, 200]
    assert records[0]["answer"] == "zero" and "answer" not in records[2]
    for rec, (offset, _) in zip(records, plan["schedule"]):
        assert rec["due"] == pytest.approx(report["t0"] + offset)
        assert rec["due"] <= rec["sent"] <= rec["done"]
    assert report["end"] >= max(r["done"] for r in records)


def test_generator_imports_nothing_but_the_standard_library():
    code = ("import sys; sys.modules['jax'] = None; "
            "sys.modules['numpy'] = None; import runpy; "
            "runpy.run_path(%r)" % loadgen.__file__)
    assert subprocess.run([sys.executable, "-c", code],
                          timeout=60).returncode == 0
