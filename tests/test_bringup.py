"""Bring-up contracts (ISSUE 21): nothing hides the device.

The compile cache can be placed from outside, the warm-up covers every
shape the server can dispatch, the serve front stays off JAX, and
``chip_smoke.py`` refuses to pass without an accelerator.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

from ingress_plus_tpu.compiler.ruleset import compile_ruleset
from ingress_plus_tpu.compiler.seclang import parse_seclang
from ingress_plus_tpu.models.pipeline import DetectionPipeline
from ingress_plus_tpu.serve.normalize import Request

REPO = Path(__file__).resolve().parent.parent

RULES = """
SecRule ARGS|REQUEST_BODY "@rx (?i)union\\s+select" "id:1,phase:2,block,t:urlDecodeUni,t:htmlEntityDecode,t:lowercase,severity:CRITICAL,tag:'attack-sqli'"
SecRule ARGS|REQUEST_BODY|REQUEST_HEADERS "@rx (?i)<script[^>]*>" "id:2,phase:2,block,t:urlDecodeUni,t:htmlEntityDecode,severity:CRITICAL,tag:'attack-xss'"
SecRule REQUEST_URI|ARGS "@rx /etc/(?:passwd|shadow)" "id:3,phase:2,block,severity:CRITICAL,tag:'attack-lfi'"
"""


# ------------------------------------------------------- compile cache

_CACHE_PROBE = """
import json, os, sys
from ingress_plus_tpu.utils.platform import enable_compile_cache
import jax
returned = enable_compile_cache()
import jax.numpy as jnp
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
print(json.dumps({"returned": returned,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _run_cache_probe(env_extra, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO) + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         cwd=str(tmp_path), capture_output=True, text=True,
                         timeout=120, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_env_dir_is_left_to_jax(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the helper sets no directory in
    code (JAX reads the variable), and the cache fills THERE."""
    there = tmp_path / "placed"
    got = _run_cache_probe({"JAX_COMPILATION_CACHE_DIR": str(there)},
                           tmp_path)
    assert got["returned"] == str(there)
    assert got["config"] == str(there)     # from the env, not from code
    assert any(there.iterdir())


def test_compile_cache_explicit_cpu_run_gets_none(tmp_path):
    """No variable and an explicit CPU run: no cache (XLA:CPU's cache
    loader floods stderr on every hit, and compiles in milliseconds)."""
    got = _run_cache_probe({}, tmp_path)
    assert got["returned"] is None and got["config"] is None


def test_compile_cache_helper_sets_the_checkout_dir_only_without_env(
        monkeypatch):
    """Unset, the helper points jax_compilation_cache_dir at the fixed
    <checkout>/.jax_cache; set, it leaves the directory to JAX."""
    import jax

    from ingress_plus_tpu.utils import platform

    assert platform.REPO_CACHE_DIR == REPO / ".jax_cache"
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    # stand in for a machine whose JAX picks its accelerator
    monkeypatch.setattr(type(jax.config), "jax_platforms",
                        property(lambda self: None), raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert platform.enable_compile_cache() == "/some/dir"
    assert "jax_compilation_cache_dir" not in updates
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert platform.enable_compile_cache() == str(platform.REPO_CACHE_DIR)
    assert updates["jax_compilation_cache_dir"] == \
        str(platform.REPO_CACHE_DIR)
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0


# --------------------------------------------------------- warm-up grid

def _tier_spanning_requests(n_each=2):
    """Requests whose scan rows land in every one of the six L tiers,
    with encodings that split a stream into several variant rows."""
    reqs = []
    for size in (30, 100, 200, 400, 1500, 9000):
        filler = ("lorem ipsum dolor " * (size // 18 + 1))[:size]
        for i in range(n_each):
            reqs.append(Request(
                method="POST", uri="/p?x=%d" % i,
                headers={"host": "h", "content-type": "text/plain"},
                body=(filler + " 1 union select 2" * i).encode()))
            reqs.append(Request(
                method="GET",
                uri="/q?a=" + "%3Cscript%3E&amp;b=%2520" + filler[:size // 2],
                headers={"host": "h", "cookie": filler[:size // 3]}))
    return reqs


def test_warmup_covers_every_shape_the_server_can_dispatch():
    """After the shape-derived warm-up, a request set spanning all six
    L tiers — in cycle sizes from 1 to max_batch — compiles nothing:
    not by the shape accounting behind ipt_engine_recompiles_total, and
    not by JAX's own backend-compile event, which also sees eager
    per-shape programs (a concatenate of the buckets' match words once
    compiled per bucket combination, invisible to the gauge)."""
    from ingress_plus_tpu.serve.server import warmup_pipeline
    from ingress_plus_tpu.utils.platform import backend_compiles

    pipe = DetectionPipeline(compile_ruleset(parse_seclang(RULES)),
                             mode="block")
    max_batch = 16
    backend_compiles()                       # start counting
    warmup_pipeline(pipe, max_batch)
    pipe.reset_detection_observations()
    warm = set(pipe._seen_exec)
    reqs = _tier_spanning_requests()
    compiled = backend_compiles()
    assert compiled > 0                      # the warm-up compiled
    for size in (1, 3, 4, 7, 16):
        for i in range(0, len(reqs), size):
            pipe.detect(reqs[i:i + size])
    assert set(pipe.stats.bucket_rows) == set(pipe.L_BUCKETS)
    assert pipe.stats.engine_compiles == 0
    assert set(pipe._seen_exec) == warm      # a superset, exactly
    assert backend_compiles() == compiled    # and XLA agrees


def test_warm_signatures_bound_comes_from_shapes():
    """The row-tier ceiling is rows-per-request x max_batch — what the
    variants a pack needs can produce, not what a corpus contained."""
    pipe = DetectionPipeline(compile_ruleset(parse_seclang(RULES)))
    rpr = sum(len(v) for v in pipe._variants_for.values())
    sigs = pipe.warm_signatures(16)
    scan = {b for buckets, _q in sigs for b in buckets}
    assert {L for _B, L in scan} == set(pipe.L_BUCKETS)
    assert max(B for B, _L in scan) == pipe._pad_q(16 * rpr, floor=8)
    assert {q for _b, q in sigs} == {4, 8, 16}


# ------------------------------------------------- one process per chip

def test_serve_front_never_initialises_a_backend():
    """`serve --front` owns no detection state: importing and parsing
    its way to the front loop must not touch a JAX backend (a front
    that did would take the chip from the node next to it)."""
    code = (
        "import sys\n"
        "sys.argv = ['serve', '--front', '--socket', '/nonexistent/x',\n"
        "            '--backend', 'n0=/nonexistent/n0.sock']\n"
        "from ingress_plus_tpu.serve import server, front\n"
        "front.FrontLoop.run_forever = lambda self: None\n"
        "import asyncio\n"
        "asyncio.run = lambda coro: None\n"
        "server.main()\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "print('front-off-jax')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "front-off-jax" in out.stdout


def test_chip_smoke_fails_without_an_accelerator():
    """The chip check's default behaviour on this CPU sandbox: non-zero
    exit before serving a request, and no result line."""
    env = dict(os.environ)
    env.pop("CHIP_SMOKE_REHEARSAL", None)
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         cwd=str(REPO), env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert '"ok"' not in out.stdout
    assert "requests answered" not in out.stdout


def test_chip_smoke_fails_outside_a_checkout(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text())
    out = subprocess.run([sys.executable, "chip_smoke.py"],
                         cwd=str(tmp_path), capture_output=True, text=True,
                         timeout=60)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_bench_default_run_fails_without_an_accelerator():
    """`python bench.py` with no chip: non-zero exit, no number."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_PLATFORM", None)
    out = subprocess.run([sys.executable, str(REPO / "bench.py")],
                         cwd=str(REPO), env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no accelerator" in out.stderr
