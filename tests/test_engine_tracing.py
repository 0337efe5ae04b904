"""The serving path's program spans, read back from a profiler trace.

A tiny engine runs two steps under ``jax.profiler.trace`` on the CPU,
over lane-packed int4 weights and over int3 streams fetched through a
:class:`~repro.engine.StreamUploader`.  The ``.xplane.pb`` file is read
with ``jax.profiler.ProfileData``: the spans of ``repro.engine.trace``
must nest as documented there.
"""
import glob
import os

import pytest

from repro.engine import (
    STAGES,
    Engine,
    EngineConfig,
    EngineRequest,
    PackedAdapter,
    StreamUploader,
)

N_LAYERS = 3                            # more than the ring's two buffers
LAYER_PARTS = ("repro.layer.qkv", "repro.layer.kv_write",
               "repro.layer.attend", "repro.layer.mlp")
MODEL_PARTS = ("repro.model.embed", "repro.model.head", "repro.model.state")


@pytest.fixture(scope="module")
def trees():
    import jax

    from repro import api
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.quant import QuantSpec

    cfg = get_config("smollm-135m").reduced(
        n_layers=N_LAYERS, d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
        vocab_size=128)
    params = Model(cfg, remat="none").init(jax.random.PRNGKey(0))
    return cfg, {bits: api.pack_tree(cfg, params,
                                     QuantSpec(bits=bits, group_size=32),
                                     m=512)
                 for bits in (3, 4)}


def _host_lines(log_dir):
    """``[name, start_ns, end_ns, stats]`` of every host event, one list
    per host line (thread), the line of the engine's steps first."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            lines += [sorted(([e.name, e.start_ns,
                               e.start_ns + e.duration_ns,
                               dict(e.stats) if e.name.startswith("repro.")
                               else {}]
                              for e in line.events),
                             key=lambda e: (e[1], -e[2]))
                      for line in plane.lines]
    lines.sort(key=lambda ev: not any(e[0] == "repro.engine.step"
                                      for e in ev))
    return lines


def _traced_steps(adapter, log_dir, n_steps=2):
    """Two slots decoding; one step untraced (compiles), then
    ``n_steps`` traced; returns the host lines."""
    import jax

    eng = Engine(adapter, EngineConfig(batch_size=2, max_seq=16))
    for uid in range(2):
        eng.submit(EngineRequest(uid=uid, prompt=[3 + uid],
                                 max_new_tokens=n_steps + 2))
    eng.step()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=options):
        for _ in range(n_steps):
            eng.step()
    return _host_lines(log_dir)


def _inside(events, outer, name):
    return [e for e in events if e[0] == name
            and outer[1] <= e[1] and e[2] <= outer[2]]


def _check_step_spans(events, n_steps=2):
    steps = [e for e in events if e[0] == "repro.engine.step"]
    assert [s[3]["step_num"] for s in steps] == list(range(1, 1 + n_steps))
    for st in steps:
        stages = [e for e in events if e[0].startswith("repro.engine.")
                  and e[0] != "repro.engine.step"
                  and st[1] <= e[1] and e[2] <= st[2]]
        assert [e[0] for e in stages] == [f"repro.engine.{s}"
                                           for s in STAGES]
        decode = stages[STAGES.index("decode")]
        adapter, = _inside(events, decode, "repro.adapter.step")
        logits, = _inside(events, adapter, "repro.adapter.logits")
        for name in MODEL_PARTS:
            assert len(_inside(events, adapter, name)) == 1, name
        for name in LAYER_PARTS:
            parts = _inside(events, adapter, name)
            assert [p[3]["layer"] for p in parts] == list(range(N_LAYERS))
        # the pull of the logits closes the step's decode call
        assert logits[1] >= max(e[2] for e in events
                                if e[0] == "repro.model.state"
                                and adapter[1] <= e[1] <= adapter[2])
    return steps


def test_packed_engine_spans(trees, tmp_path):
    cfg, by_bits = trees
    events = _traced_steps(PackedAdapter(cfg, by_bits[4]),
                           str(tmp_path))[0]
    steps = _check_step_spans(events)
    assert not [e for e in events if e[0].startswith("repro.stream.")]
    # the JAX launches of the decode call sit inside the program's spans
    adapter = _inside(events, steps[0], "repro.adapter.step")[0]
    launches = [e for e in events if e[0].startswith("PjitFunction(")
                and adapter[1] <= e[1] < adapter[2]]
    assert launches


def test_stream_engine_spans(trees, tmp_path):
    cfg, by_bits = trees
    with StreamUploader(by_bits[3]) as up:
        lines = _traced_steps(PackedAdapter(cfg, by_bits[3], uploader=up),
                              str(tmp_path))
    events = lines[0]
    steps = _check_step_spans(events)
    waits = 0
    for st in steps:
        adapter, = _inside(events, st, "repro.adapter.step")
        layers = [e[3]["layer"] for e in
                  _inside(events, adapter, "repro.stream.wait")]
        assert len(layers) == len(set(layers))      # once per layer at most
        assert set(layers) <= set(range(N_LAYERS))
        waits += len(layers)
    # the ring holds two of three layers: each step waits on uploads
    assert waits > 0
    # prefetched uploads run on the uploader's thread
    assert any(e[0] == "repro.stream.upload" for ev in lines[1:] for e in ev)
