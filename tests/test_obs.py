"""The serving path's spans and request stamps (serving/obs.py): a span
records nothing outside a profiler session; inside one, the program's
stamps fall inside the spans that took them, on the trace's own clock;
and the stamps keep their order across a preemption and a KV-attach
re-admission."""

import glob
import os

import jax
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serving import GlobalServer, ServeRequest, TensorStore, obs

SPANS = {"serve.submit", "serve.step", "serve.pipeline", "serve.kv_attach",
         "serve.preempt", "serve.prefix_publish", "serve.admit",
         "serve.admit.plan", "serve.prefill", "serve.suffix", "serve.cow",
         "serve.readmit", "serve.chunk", "serve.grow", "serve.decode",
         "serve.sync"}


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("internlm2-1.8b").reduced()
    params = build_model(cfg, remat=False, attn_chunk=0).init(
        jax.random.PRNGKey(0))
    return cfg, params


def test_span_outside_a_session_is_one_shared_noop():
    a = obs.span("serve.decode", rows=3)
    with obs.span("serve.admit") as b:
        b.set_metadata(n=2)
    assert a is b
    assert not obs.recording()
    assert abs(obs.now_ns() - obs.now_ns()) < 10**9


def _host_spans(log_dir):
    """(profile start on the host clock, [(name, start, end, stats)]) of
    every ``serve.*`` host event, times relative to the profile start."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = ProfileData.from_file(path)
    t0, spans = None, []
    for plane in pd.planes:
        for k, v in plane.stats:
            if k == "profile_start_time":
                t0 = int(v)
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  dict(ev.stats)))
    return t0, spans


def test_stamps_lie_inside_their_spans_on_the_trace_clock(setup, tmp_path):
    cfg, params = setup
    srv = GlobalServer(cfg, None, max_batch=4, max_len=64, prefill_chunk=16)
    srv.add_pipeline(params, ["inst-A"])
    reqs = [ServeRequest(prompt=list(range(1, 9)), max_new_tokens=4),
            ServeRequest(prompt=list(range(1, 41)), max_new_tokens=4)]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert obs.recording()
        for r in reqs:
            srv.submit(r)
        while srv.pending():
            srv.step()
    finally:
        jax.profiler.stop_trace()
    assert srv.pipelines[0].engine.stats.chunk_direct >= 3   # one chunked
    t0, spans = _host_spans(str(tmp_path))
    assert t0 is not None
    names = {s[0] for s in spans}
    assert names <= SPANS, names - SPANS
    assert {"serve.submit", "serve.step", "serve.pipeline", "serve.admit",
            "serve.admit.plan", "serve.prefill", "serve.chunk",
            "serve.decode", "serve.sync", "serve.grow"} <= names

    def inside(stamp, name, **stats):
        rel = stamp - t0
        return [s for s in spans if s[0] == name and s[1] <= rel <= s[2]
                and all(s[3].get(k) == v for k, v in stats.items())]

    for r in reqs:
        assert 0 < r.submit_ns <= r.admit_ns <= r.first_token_ns \
            <= r.finish_ns
        assert inside(r.submit_ns, "serve.submit", rid=r.rid)
        assert inside(r.admit_ns, "serve.admit")
        assert inside(r.first_token_ns, "serve.sync", what="first")
        assert inside(r.finish_ns, "serve.sync")
    # span meta: the chunked prompt's chunks and the decode rows
    chunks = sorted((s[3]["base"], s[3]["tokens"]) for s in spans
                    if s[0] == "serve.chunk")
    assert chunks == [(0, 16), (16, 16), (32, 8)]
    assert [s[3]["rows"] for s in spans if s[0] == "serve.prefill"] == [1]
    assert max(s[3]["rows"] for s in spans if s[0] == "serve.decode") == 2
    # pages: each live row's ceil(ctx_len / 16), its live pool blocks;
    # with both rows live the 40-token prompt holds 3 blocks, the other 1
    assert max(s[3]["pages"] for s in spans if s[0] == "serve.decode") == 4
    admits = [s[3]["n"] for s in spans if s[0] == "serve.admit"]
    assert sum(admits) == 2


def test_stamps_keep_order_across_preemption_and_kv_attach(setup):
    cfg, params = setup
    srv = GlobalServer(cfg, TensorStore(), max_batch=4, max_len=64,
                       use_kv_migration=True,
                       engine_kw={"block_size": 8, "n_blocks": 11,
                                  "kv_overcommit": 2.5})
    srv.add_pipeline(params, ["inst-A"])
    reqs = [ServeRequest(prompt=list(range(1, 10 + 2 * i)),
                         max_new_tokens=20) for i in range(3)]
    for r in reqs:
        srv.submit(r)
    first_admit = {}
    while srv.pending():
        srv.step()
        for r in reqs:
            if r.admit_ns:
                first_admit.setdefault(r.rid, r.admit_ns)
    kinds = [k for _, k, _ in srv.events]
    assert kinds.count("preempt") >= 1 and kinds.count("kv_attach") >= 1
    for r in reqs:
        assert r.done
        assert 0 < r.submit_ns <= r.admit_ns <= r.first_token_ns \
            <= r.finish_ns
        assert r.admit_ns == first_admit[r.rid]     # re-admission keeps it
