"""Traffic from a seed, the zipfian helper, and BENCHMARK.json's form."""

import json
import os
import re

import numpy as np
import pytest

from chipbench.bench import gen, harness
from chipbench.loops import closed_keyed, closed_stream

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./\-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


class StubSystem:
    """Records what a loop sends; answers reads with zeros."""

    def __init__(self, rows=512):
        self.rows = rows
        self.sent = []

    def submit(self, keys, items):
        self.sent.append((np.array(keys), np.array(items)))

    def flush(self):
        pass

    def read(self):
        return np.zeros(self.rows)

    def update(self, items):
        self.sent.append(np.array(items))

    def estimate(self):
        return 0.0


def _traffic(name):
    return harness.load_cell(
        {"ingest": "tenants.ingest", "stream": "paper.stream", "small_batch": "paper.small_batch"}[name],
        rehearsal=True,
    ).traffic


def _draw(loop_mod, traffic, seed):
    system = StubSystem()
    loop = loop_mod.Loop(traffic, system, harness._rng(seed))
    return system, loop


def _same(a, b):
    if isinstance(a, tuple):
        return all(np.array_equal(x, y) for x, y in zip(a, b))
    return np.array_equal(a, b)


@pytest.mark.parametrize("mix,mod", [
    ("ingest", closed_keyed), ("stream", closed_stream), ("small_batch", closed_stream),
])
def test_seed_fixes_traffic(mix, mod):
    traffic = _traffic(mix)
    a, la = _draw(mod, traffic, 2**31 + 11)
    b, lb = _draw(mod, traffic, 2**31 + 11)
    c, lc = _draw(mod, traffic, 2**31 + 12)
    if mod is closed_stream:
        assert np.array_equal(la.pool, lb.pool)
        assert not np.array_equal(la.pool, lc.pool)
    else:
        for t in range(3):
            assert _same(la.pairs(t), lb.pairs(t))
            assert not _same(la.pairs(t), lc.pairs(t))
    assert len(a.sent) == len(b.sent)
    assert all(_same(x, y) for x, y in zip(a.sent, b.sent))


def test_no_two_ingest_ticks_repeat_a_pair_set():
    _, loop = _draw(closed_keyed, _traffic("ingest"), 5)
    slots = loop.keys.shape[0]
    seen = set()
    for t in range(3 * slots + 1):
        keys, items = loop.pairs(t)
        packed = np.unique((keys.astype(np.uint64) << np.uint64(32)) | items)
        digest = packed.tobytes()
        assert digest not in seen, f"tick {t} repeats an earlier tick's pairs"
        seen.add(digest)


def _ycsb_next(u, n, theta):
    """YCSB ZipfianGenerator.nextLong (core/.../ZipfianGenerator.java), one
    draw from the uniform ``u``, base 0."""
    zetan = sum(1.0 / (i + 1) ** theta for i in range(n))
    zeta2 = sum(1.0 / (i + 1) ** theta for i in range(2))
    alpha = 1.0 / (1.0 - theta)
    eta = (1 - (2.0 / n) ** (1 - theta)) / (1 - zeta2 / zetan)
    uz = u * zetan
    if uz < 1.0:
        return 0
    if uz < 1.0 + 0.5**theta:
        return 1
    return int(n * (eta * u - eta + 1) ** alpha)


class FixedUniforms:
    def __init__(self, u):
        self.u = np.asarray(u)

    def random(self, size):
        assert size == self.u.size
        return self.u


@pytest.mark.parametrize("n", [3, 10, 100, 1000])
def test_zipfian_matches_ycsb(n):
    u = np.random.default_rng(n).random(2000)
    got = gen.ycsb_zipfian(n, u.size, FixedUniforms(u), 0.99)
    want = [_ycsb_next(x, n, 0.99) for x in u]
    assert got.tolist() == want
    assert got.min() >= 0 and got.max() < n


def test_zipfian_skew_at_small_n():
    n, draws = 10, 200_000
    got = gen.ycsb_zipfian(n, draws, np.random.default_rng(1), 0.99)
    share = np.bincount(got, minlength=n) / draws
    pmf = 1.0 / np.arange(1, n + 1) ** 0.99
    pmf /= pmf.sum()
    assert share[0] == pytest.approx(pmf[0], abs=0.01)
    assert share[1] == pytest.approx(pmf[1], abs=0.01)
    assert (np.diff(share[:3]) < 0).all()


# ---------------------------------------------------------------- the file


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_units_use_allowed_characters(bench):
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [w["name"] for w in bench["workloads"]] + [c["name"] for c in bench["configs"]]
    names += [w["config"] for w in bench["workloads"]] + [w["traffic"] for w in bench["workloads"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    for name in names:
        assert NAME.match(name), name
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    assert len(set(names[: len(bench["end_to_end"]) + len(bench["per_layer"])])) == (
        len(bench["end_to_end"]) + len(bench["per_layer"])
    )


def test_benchmark_file_form(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert PATH.match(p) and ".." not in p and not p.startswith("/")
    assert len(bench["command"]) <= 32
    for word in bench["command"]:
        assert 1 <= len(word) <= 200 and "\n" not in word and "\t" not in word
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert os.path.isfile(os.path.join(harness.ROOT, c["file"]))
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] and cfg["guarantees"] and "assumed" in cfg
        assert cfg["reduced"] == c["reduced"]
    configs = {c["name"] for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(harness.BENCH_DIR, "traffic", w["traffic"] + ".json"))
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
        assert set(m["workloads"]) <= cells
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", [cell])
        assert os.path.isfile(os.path.join(harness.BENCH_DIR, "metrics", m["name"] + ".py"))
    for cell in cells:
        reported = [n for n, m in e2e.items() if cell in m.get("workloads", [cell])]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in m["workloads"] for m in bench["per_layer"])
    assert len(json.dumps(bench)) <= 64 * 1024


def test_cells_carry_the_named_parameters():
    tenants = harness.load_cell("tenants.ingest").config
    assert (tenants["p"], tenants["hash_bits"], tenants["tenants"]) == (12, 64, 1 << 18)
    assert tenants["start_state"]["history_pairs"] == 1 << 24
    assert tenants["start_state"]["keys"] == {"dist": "zipfian", "theta": 0.99}
    ingest = harness.load_cell("tenants.ingest").traffic
    assert (ingest["tick_pairs"], ingest["submits_per_tick"]) == (1 << 22, 4096)
    assert ingest["keys"] == {"dist": "zipfian", "theta": 0.99}
    paper = harness.load_cell("paper.stream").config
    assert (paper["p"], paper["hash_bits"]) == (16, 64)
    assert harness.load_cell("paper.stream").traffic["chunk_items"] == 1 << 24
    assert harness.load_cell("paper.small_batch").traffic["chunk_items"] == 1 << 14
    for cell in ("paper.stream", "paper.small_batch"):
        assert harness.load_cell(cell).traffic["pool_items"] == 1 << 28
