"""The bridge from kfx's spans to the profiler's clock (obs/trace.py):
a process that owns a device registers an annotation factory once, and
``span`` / ``start_span`` / ``annotate`` open it beside what they
record. Driven here with a fake factory; tests/test_engine_trace.py
drives the real one (``jax.profiler.TraceAnnotation``) under a profiler
session."""

import os
import subprocess
import sys

import pytest

from kubeflow_tpu.obs import trace as obs_trace


class _Recorder:
    """A factory that logs every annotation it is asked for."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **attrs):
        rec = self

        class _Annotation:
            def __enter__(self):
                rec.log.append(("open", name, attrs))
                return self

            def __exit__(self, *exc):
                rec.log.append(("close", name, attrs))
                return False

        return _Annotation()


@pytest.fixture
def factory():
    rec = _Recorder()
    obs_trace.set_annotation_factory(rec)
    yield rec
    obs_trace.set_annotation_factory(None)


def _with_span():
    with obs_trace.span("engine.chunk", model="m", slots="3"):
        with obs_trace.span("engine.inner"):
            pass


def _with_start_finish():
    outer = obs_trace.start_span("engine.chunk", model="m", slots=3)
    inner = obs_trace.start_span("engine.inner")
    obs_trace.finish_span(inner)
    obs_trace.finish_span(outer, status="error")


def _with_annotate():
    with obs_trace.annotate("engine.chunk", model="m", slots="3"):
        with obs_trace.annotate("engine.inner"):
            pass


NESTED = [("open", "engine.chunk"), ("open", "engine.inner"),
          ("close", "engine.inner"), ("close", "engine.chunk")]


@pytest.mark.parametrize("body", [_with_span, _with_start_finish,
                                  _with_annotate],
                         ids=["span", "start_finish", "annotate"])
def test_annotations_open_and_close_in_order_with_the_attributes(
        factory, body):
    body()
    assert [(what, name) for what, name, _ in factory.log] == NESTED
    attrs = factory.log[0][2]
    assert {k: str(v) for k, v in attrs.items()} == \
        {"model": "m", "slots": "3"}
    assert factory.log[1][2] == {}


@pytest.mark.parametrize("opener", [obs_trace.span, obs_trace.annotate],
                         ids=["span", "annotate"])
def test_annotations_close_when_the_body_raises(factory, opener):
    with pytest.raises(KeyError):
        with opener("engine.chunk", model="m"):
            with opener("engine.inner"):
                raise KeyError("boom")
    assert [(what, name) for what, name, _ in factory.log] == NESTED
    # and no span is left open on this thread
    assert not getattr(obs_trace._tls, "span_stack", [])


def test_a_span_still_records_beside_its_annotation(factory, tmp_path):
    was = obs_trace._sink, obs_trace._sink_resolved
    obs_trace._sink = None      # (set_span_sink closes the one it finds)
    obs_trace.set_span_sink(str(tmp_path), "bridge-test")
    try:
        with obs_trace.span("engine.chunk", trace_id="t" * 16) as sp:
            pass
    finally:
        obs_trace._sink.close()
        obs_trace._sink, obs_trace._sink_resolved = was
    assert sp.duration >= 0 and sp.status == "ok"
    text = (tmp_path / f"bridge-test-{os.getpid()}.jsonl").read_text()
    assert '"name":"engine.chunk"' in text
    assert [w for w, _, _ in factory.log] == ["open", "close"]


def test_with_no_factory_nothing_is_called():
    obs_trace.set_annotation_factory(None)
    assert obs_trace.annotate("a") is obs_trace.annotate("b", k=1)
    with obs_trace.annotate("a"):
        with obs_trace.annotate("a"):   # the shared context re-enters
            pass
    sp = obs_trace.start_span("engine.chunk")
    assert sp._annotation is None
    obs_trace.finish_span(sp)


def test_a_factory_registered_mid_span_closes_nothing_it_did_not_open():
    obs_trace.set_annotation_factory(None)
    sp = obs_trace.start_span("runner.init")   # before jax is imported
    rec = _Recorder()
    obs_trace.set_annotation_factory(rec)
    try:
        obs_trace.finish_span(sp)
        assert rec.log == []
        with obs_trace.span("checkpoint.save"):
            pass
        assert [w for w, _, _ in rec.log] == ["open", "close"]
    finally:
        obs_trace.set_annotation_factory(None)


def test_the_trace_module_does_not_import_jax():
    """The plane imports obs/trace.py and must never load jax (a chip
    has one owner; tests/test_operators.py pins the whole plane)."""
    code = ("import sys\n"
            "from kubeflow_tpu.obs import trace\n"
            "with trace.span('x'), trace.annotate('y', k=1): pass\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
