"""The program's spans (``repro.core.spans``) as a profiler trace records
them: the tree ``Server.generate`` writes, the executor's plan span on a
cache miss only, and garbage collections as ``py.gc``."""
import gc
import glob
import os

import numpy as np

import jax

from repro.core import Executor, Program, spans
from repro.models import ArchConfig, Model
from repro.runtime import Server, ServeConfig

CFG = ArchConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=128, vocab=256)
PREFIXES = ("serve.", "ntx.", "py.")


def _traced(tmp_path, fn):
    """``fn()``'s result and the program's spans it wrote, as
    ``(name, start_ns, end_ns, stats)`` in start order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        out = fn()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    found = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(PREFIXES)]
    return out, sorted(found, key=lambda s: (s[1], -s[2]))


def _inside(outer, spans_):
    """The spans that lie within ``outer`` (itself left out)."""
    return [s for s in spans_ if s is not outer
            and outer[1] <= s[1] and s[2] <= outer[2]]


def _names(spans_):
    return [s[0] for s in spans_]


def test_generate_writes_the_span_tree(tmp_path):
    new = 3
    srv = Server(CFG, Model(CFG).init(0),
                 ServeConfig(max_seq=16, max_new_tokens=new, eos_token=-1))
    prompts = [np.arange(6) % 256, (np.arange(6) + 5) % 256]
    srv.generate(prompts)                           # compile outside the trace
    out, found = _traced(tmp_path, lambda: srv.generate(prompts))
    assert set(out) == {"completions", "prefill_s", "decode_tok_per_s",
                        "steps_ahead", "steps_discarded"}
    serve = [s for s in found if s[0].startswith("serve.")]
    (call,) = [s for s in serve if s[0] == "serve.generate"]
    assert isinstance(call[3]["call"], int)
    assert (call[3]["batch"], call[3]["prompt_len"]) == (2, 6)
    assert _inside(call, serve) == serve[1:]        # every serve span in it

    (prefill,) = [s for s in serve if s[0] == "serve.prefill"]
    inner = [s for s in _inside(prefill, serve)]
    assert _names(inner) == ["serve.forward", "serve.sample"]
    assert [s[3].get("phase") for s in inner] == ["prefill", "prefill"]
    # the host's clock agrees: first tokens on the host come after the
    # prefill's dispatch
    assert out["prefill_s"] * 1e9 >= prefill[2] - prefill[1]
    assert out["prefill_s"] * 1e9 <= call[2] - call[1]

    steps = [s for s in serve if s[0] == "serve.step"]
    assert [s[3]["step"] for s in steps] == list(range(new))
    assert [s[3]["active"] for s in steps] == [2] * new
    for i, step in enumerate(steps):
        assert step[1] >= prefill[2]
        inner = _inside(step, serve)
        tail = ["serve.readback", "serve.emit"]
        if i + 1 < new:                 # the next step's forward and sample
            assert _names(inner) == ["serve.forward", "serve.sample"] + tail
            assert [s[3].get("phase") for s in inner[:2]] == ["decode",
                                                              "decode"]
            # the greedy sampler is a device program, not a descriptor run
            assert "ntx.run" not in _names(_inside(inner[1], found))
        else:                           # the last token needs no forward
            assert _names(inner) == tail
        # the readback copies the sampled ids to the host, 4 B a row
        (readback,) = [s for s in inner if s[0] == "serve.readback"]
        assert readback[3]["bytes"] == 4 * len(prompts)


def test_generate_reads_ids_one_step_behind(tmp_path):
    """No host sync between one decode step and the next: the ids of
    step i are read after the forward that consumes them is dispatched,
    so step i+1 is queued on the device while the host reads."""
    new = 4
    srv = Server(CFG, Model(CFG).init(0),
                 ServeConfig(max_seq=16, max_new_tokens=new, eos_token=-1))
    prompts = [np.arange(5) % 256, (np.arange(5) + 9) % 256]
    srv.generate(prompts)                           # compile outside the trace
    out, found = _traced(tmp_path, lambda: srv.generate(prompts))
    forwards = [s for s in found if s[0] == "serve.forward"]
    readbacks = [s for s in found if s[0] == "serve.readback"]
    # the prefill's forward, then one a step but the last
    assert [s[3]["phase"] for s in forwards] == ["prefill"] + ["decode"] * (
        new - 1)
    assert len(readbacks) == new
    # forwards[i + 1] makes the logits of token i + 1 from token i's ids
    for i in range(new - 1):
        assert readbacks[i][1] >= forwards[i + 1][1]
    (call,) = [s for s in found if s[0] == "serve.generate"]
    assert call[3]["steps_ahead"] == new - 1 == out["steps_ahead"]
    assert call[3]["steps_discarded"] == 0 == out["steps_discarded"]


def test_executor_plans_once_per_program(tmp_path):
    p = Program()
    x = p.buffer(64, name="x")
    p.relu(x, out=x)
    ex = Executor(policy="fused")
    img = np.linspace(-1, 1, 64, dtype=np.float32)

    def two_runs():
        return [ex.run(p, {x: img})[x] for _ in range(2)]

    out, found = _traced(tmp_path, two_runs)
    np.testing.assert_array_equal(out[1], np.maximum(img, 0))
    found = [s for s in found if s[0].startswith("ntx.")]
    runs = [s for s in found if s[0] == "ntx.run"]
    assert len(runs) == 2
    assert _names(_inside(runs[0], found)) == ["ntx.pack", "ntx.plan",
                                               "ntx.execute", "ntx.unpack"]
    assert _names(_inside(runs[1], found)) == ["ntx.pack", "ntx.execute",
                                               "ntx.unpack"]


def test_run_descriptors_spans(tmp_path):
    p = Program()
    x = p.buffer(32, name="x")
    p.relu(x, out=x)
    ex = Executor(policy="serial")
    _, found = _traced(tmp_path, lambda: ex.run_descriptors(
        p.descriptors, np.ones(p.size, np.float32)))
    (run,) = [s for s in found if s[0] == "ntx.run"]
    assert _names(_inside(run, [s for s in found
                                if s[0].startswith("ntx.")])) == [
        "ntx.execute"]


def test_gc_span_installs_once_and_records_collections(tmp_path):
    spans.install_gc_span()
    spans.install_gc_span()
    assert gc.callbacks.count(spans._gc_callback) == 1
    _, found = _traced(tmp_path, lambda: gc.collect())
    collections = [s for s in found if s[0] == "py.gc"]
    assert any(s[3]["gen"] == 2 for s in collections)
