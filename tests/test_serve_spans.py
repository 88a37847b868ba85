"""The serve engine's instrumentation on the profiler's clock: the
tracer's spans, the forward and prefill counters, and named scopes
that change no device code."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.serve import EngineConfig, ServeEngine, paged_model, sampler
from repro.serve.obs import Tracer


def test_span_is_the_shared_noop_when_not_profiling(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("a TraceAnnotation was built")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    t, other = Tracer(), Tracer(level="trace")
    a = t.span("serve.fund")
    assert a is t.span("serve.pack", phase="decode") is other.span("x")
    with a:
        pass


def test_span_is_a_trace_annotation_when_profiling():
    t = Tracer()
    t.profiling, t.step_index = True, 3
    span = t.span("serve.pack", phase="decode")
    assert isinstance(span, jax.profiler.TraceAnnotation)
    with span:
        pass


def engine(arch: str) -> ServeEngine:
    cfg = configs.get_config(arch, smoke=True)
    ecfg = EngineConfig(page_size=4, n_pages=128, max_batch=4,
                        max_pages_per_seq=16, prefill_chunk=8,
                        max_seq_len=64)
    return ServeEngine(cfg, ecfg=ecfg, seed=0)


@pytest.mark.parametrize("arch", ["qwen3_8b", "rwkv6_3b"])
def test_forward_and_prefill_counters(arch):
    eng = engine(arch)
    calls = {"decode": 0, "prefill": 0}
    be = eng.backend
    for phase in calls:
        fn = getattr(be, f"_{phase}_fn")

        def counted(*a, _fn=fn, _phase=phase):
            calls[_phase] += 1
            return _fn(*a)

        setattr(be, f"_{phase}_fn", counted)
    prompts = [list(range(1 + i, 14 + 3 * i)) for i in range(3)]
    for p in prompts:
        eng.submit(p, 4)
    steps = 0
    while eng.step() is not None:
        steps += 1
    assert all(r.done for r in eng.requests.values())
    reg = eng.obs.registry
    assert eng.obs.step_index == steps + 1       # the last call found no work
    assert reg.count("engine/decode_forwards") == calls["decode"] > 0
    assert reg.count("engine/prefill_forwards") == calls["prefill"] > 0
    assert reg.count("backend/prefill_positions") == calls["prefill"] * 4 * 8
    assert reg.count("backend/prefill_tokens") == sum(map(len, prompts))
    assert "engine/step_tokens" not in reg.keys()
    assert "backend/n_admissions" not in reg.keys()


def _device_code(compiled_text: str) -> str:
    """Compiled HLO without metadata and source locations."""
    txt = re.sub(r",? ?metadata=\{[^}]*\}", "", compiled_text)
    return "\n".join(line for line in txt.splitlines()
                     if not re.match(r"^\d+ ", line))


def _path_parts(compiled_text: str) -> set:
    return {part for path in re.findall(r'op_name="([^"]*)"', compiled_text)
            for part in path.split("/")}


def _programs():
    cfg = configs.get_config("qwen3_8b", smoke=True)    # two layers
    b, c, pmax, vocab = 4, 8, 16, cfg.vocab_size
    eng = ServeEngine(cfg, ecfg=EngineConfig(
        page_size=4, n_pages=64, max_batch=b, max_pages_per_seq=pmax,
        prefill_chunk=c), seed=0)
    be = eng.backend
    z = jnp.zeros

    def sample_tokens(*a):
        return sampler.sample_tokens.__wrapped__(*a)

    return {
        "decode": (jax.jit(paged_model.make_paged_decode(cfg)),
                   (be.params, z((b, 1), jnp.int32), be.cache.kv,
                    z((b, pmax), jnp.int32), z((b,), jnp.int32),
                    z((b,), bool))),
        "prefill": (jax.jit(paged_model.make_paged_chunked_prefill(cfg)),
                    (be.params, z((b, c), jnp.int32), be.cache.kv,
                     z((b, pmax), jnp.int32), z((b,), jnp.int32),
                     z((b,), jnp.int32), z((b,), bool),
                     z((b,), jnp.int32))),
        "sampler": (jax.jit(sample_tokens),
                    (z((b, vocab), jnp.bfloat16), z((b,), jnp.float32),
                     z((b,), jnp.int32), z((b,), jnp.float32),
                     z((b,), jnp.uint32), z((b,), jnp.int32))),
    }


def test_named_scopes_change_no_device_code(monkeypatch):
    scoped = {k: fn.lower(*a).compile().as_text()
              for k, (fn, a) in _programs().items()}
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = {k: fn.lower(*a).compile().as_text()
             for k, (fn, a) in _programs().items()}
    want = {"decode": {"embed", "kv_read", "attention", "mlp", "kv_write",
                       "lm_head"},
            "sampler": {"sampler"}}
    want["prefill"] = want["decode"]
    for k in scoped:
        assert want[k] <= _path_parts(scoped[k]), k
        assert not want[k] & _path_parts(plain[k]), k
        assert _device_code(scoped[k]) == _device_code(plain[k]), k


def test_spans_on_change_no_tokens():
    outs = []
    for profiling in (False, True):
        eng = engine("qwen3_8b")
        eng.obs.profiling = profiling
        for i in range(3):
            eng.submit(list(range(2 + i, 19 + 2 * i)), 5)
        eng.drain()
        outs.append(eng.results())
    for rid in outs[0]:
        np.testing.assert_array_equal(outs[0][rid], outs[1][rid])
