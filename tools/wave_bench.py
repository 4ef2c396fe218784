"""What one stream-engine scan wave costs on the chip, by width, alone and
beside a thread that never yields the interpreter lock (PERF.md §5-§6,
PR 35: the side lane's scan thread runs beside the finish thread's inline
confirm walk).

    chiprun --chips 1 -- env PYTHONPATH=. python tools/wave_bench.py

Three readings per width (2,048 / 8,192 / 16,384 / 32,768 steps, eight
rows, 131,072 steps in all, the carry on the device from wave to wave):
the program alone with its tokens resident on the device; as
``StreamEngine.scan`` launches it (tokens from the host every wave), with
the time spent inside the calls into JAX; and the same beside a busy
Python thread.  Then ``StreamEngine`` itself over a 90,000 B stream fed
64 KiB at a time.  A timing from a CPU run of this file says nothing
about the chip: it fails without an accelerator.
"""

import json
import os
import threading
import time

import numpy as np

from ingress_plus_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

import jax                                                    # noqa: E402

from ingress_plus_tpu.compiler import compile_ruleset         # noqa: E402
from ingress_plus_tpu.compiler.sigpack import (               # noqa: E402
    RULES_DIR,
    load_bundled_rules,
)
from ingress_plus_tpu.models.pipeline import DetectionPipeline  # noqa: E402
from ingress_plus_tpu.ops.scan import pad_rows, scan_bytes_jit  # noqa: E402
from ingress_plus_tpu.serve.normalize import Request          # noqa: E402
from ingress_plus_tpu.serve.stream import StreamEngine        # noqa: E402

TOTAL = 131072
WIDTHS = (2048, 8192, 16384, 32768)
ROWS, LIVE = 8, 2


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        raise SystemExit("no accelerator: a CPU timing is not a device metric")
    print("device:", dev.platform, dev.device_kind, flush=True)
    cr = compile_ruleset(load_bundled_rules(), base_path=RULES_DIR / "crs")
    pipeline = DetectionPipeline(cr, mode="block")
    tables = pipeline.engine.tables.scan
    W = cr.tables.n_words
    rng = np.random.default_rng(35)
    out = {}

    def wave_input(L):
        rows = [bytes(rng.integers(32, 127, L, dtype=np.uint8))
                for _ in range(LIVE)]
        return pad_rows(rows + [b""] * (ROWS - LIVE), max_len=L, round_to=L)

    def resident(L):
        tokens, lengths = (jax.device_put(x) for x in wave_input(L))
        state = jax.device_put(np.zeros((ROWS, W), np.uint32))
        match = jax.device_put(np.zeros((ROWS, W), np.uint32))
        jax.block_until_ready((tokens, lengths, state, match))
        t0 = time.perf_counter()
        for _ in range(TOTAL // L):
            match, state = scan_bytes_jit(tables, tokens, lengths, state,
                                          match)
        jax.block_until_ready((match, state))
        return (time.perf_counter() - t0) * 1e3

    def launched(L):
        tokens, lengths = wave_input(L)
        state = np.zeros((ROWS, W), np.uint32)
        match = np.zeros_like(state)
        t0 = time.perf_counter()
        in_calls = 0.0
        for _ in range(TOTAL // L):
            a = time.perf_counter()
            match, state = scan_bytes_jit(tables, tokens, lengths, state,
                                          match)
            in_calls += time.perf_counter() - a
        np.asarray(match)
        np.asarray(state)
        return (time.perf_counter() - t0) * 1e3, in_calls * 1e3

    engine = StreamEngine(pipeline)
    engine.warm()
    body = bytes(rng.integers(97, 123, 90000, dtype=np.uint8))

    def engine_ms():
        st = engine.begin(Request(
            method="POST", uri="/u", request_id="p",
            parsers_off=frozenset(("gzip", "base64", "json"))))
        t0 = time.perf_counter()
        for i in range(0, len(body), 65536):
            engine.scan(st.feed(body[i:i + 65536]))
        engine.scan(st.flush())
        return (time.perf_counter() - t0) * 1e3

    for L in WIDTHS:
        resident(L)
        ms = min(resident(L) for _ in range(3))
        out["resident L=%d" % L] = {"ms": round(ms, 2),
                                    "us_per_step": round(ms * 1e3 / TOTAL, 3)}
        print("resident", L, out["resident L=%d" % L], flush=True)

    stop = threading.Event()

    def busy():
        x = 0
        while not stop.is_set():
            for i in range(10000):
                x += i * i

    for mode in ("alone", "beside a busy thread"):
        thread = None
        if mode != "alone":
            thread = threading.Thread(target=busy, daemon=True)
            thread.start()
        for L in WIDTHS:
            launched(L)
            ms, in_calls = min(launched(L) for _ in range(3))
            out["%s L=%d" % (mode, L)] = {
                "ms": round(ms, 2), "ms_in_calls_into_jax": round(in_calls, 2),
                "waves": TOTAL // L}
            print(mode, L, out["%s L=%d" % (mode, L)], flush=True)
        engine_ms()
        ts = sorted(engine_ms() for _ in range(5))
        out["%s engine 90000 B" % mode] = {
            "ms_min_median_max": [round(ts[0], 2), round(ts[2], 2),
                                  round(ts[-1], 2)]}
        print(mode, "engine", out["%s engine 90000 B" % mode], flush=True)
        if thread:
            stop.set()
            thread.join()
    os.makedirs("chiprun_out/wave_bench", exist_ok=True)
    with open("chiprun_out/wave_bench/wave_bench.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
