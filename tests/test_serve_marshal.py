"""Round marshalling: a flush round's pair groups move between per-stream
arrays and the engine's stacked batch through one compiled executable each
way (``serve.svd_service._assemble_round`` / ``_split_round``, each a few
compiled calls over chunks of streams).

Pins two contracts:

* the compiled marshalling only moves data: service states after several
  rounds equal, bit for bit, those of the same service driven through an
  eager reference kept here (``stack_trees`` / ``jnp.stack`` /
  ``jnp.concatenate`` padding / ``unstack_tree``, per array);
* the registry counter ``round_marshal_traces`` counts traces, one per
  direction for a round shape new to the process and none for a repeated
  one, and a restored service traces none in its first round.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.engine import stack_trees, unstack_tree
from repro.core.svd_update import TruncatedSvd
from repro.obs import metrics as obs_metrics
from repro.serve import SvdService
from repro.serve import svd_service

REPO = Path(__file__).resolve().parent.parent


def _eager_assemble(states, a_vecs, b_vecs, n_valid, *, k):
    """The per-array eager assembly the compiled one replaced: stack the
    ``n_valid`` live rows, then pad the batch by repeating the last state
    and appending zero pairs."""
    pad = len(states) - n_valid
    t_stack = stack_trees([TruncatedSvd(*st) for st in states[:n_valid]])
    dt = t_stack.u.dtype
    m, n = t_stack.u.shape[1], t_stack.v.shape[1]
    cols = [(a_vecs[i * k:(i + 1) * k], b_vecs[i * k:(i + 1) * k])
            for i in range(n_valid)]
    if k == 1:
        a_stack = jnp.stack([jnp.asarray(a[0], dt) for a, _ in cols])
        b_stack = jnp.stack([jnp.asarray(b[0], dt) for _, b in cols])
        pad_a, pad_b = (pad, m), (pad, n)
    else:
        a_stack = jnp.stack([jnp.stack([jnp.asarray(x, dt) for x in a])
                             for a, _ in cols])
        b_stack = jnp.stack([jnp.stack([jnp.asarray(x, dt) for x in b])
                             for _, b in cols])
        pad_a, pad_b = (pad, k, m), (pad, k, n)
    if pad:
        t_stack = jax.tree.map(
            lambda x: jnp.concatenate([x, jnp.repeat(x[-1:], pad, axis=0)]),
            t_stack)
        a_stack = jnp.concatenate([a_stack, jnp.zeros(pad_a, dt)])
        b_stack = jnp.concatenate([b_stack, jnp.zeros(pad_b, dt)])
    return t_stack, a_stack, b_stack


def _eager_split(out):
    return [tuple(unstack_tree(out, j)) for j in range(out.u.shape[0])]


def _state(rng, m, n, r, dtype):
    return TruncatedSvd(
        jnp.asarray(np.linalg.qr(rng.normal(size=(m, r)))[0], dtype),
        jnp.asarray(np.sort(np.abs(rng.normal(size=r)))[::-1].copy(), dtype),
        jnp.asarray(np.linalg.qr(rng.normal(size=(n, r)))[0], dtype),
    )


# name -> (streams as (m, n, r, dtype), events a stream, flush depth, pairs
#          as numpy arrays); groups of 3 pad to a batch of 4
_F64, _F32 = jnp.float64, jnp.float32
CASES = {
    "depth1": ([(8, 10, 3, _F64)] * 4, 3, 1, False),
    "depth8": ([(8, 10, 3, _F64)] * 4, 16, 8, False),
    "padded": ([(8, 10, 3, _F64)] * 3, 3, 1, False),
    "padded_depth8": ([(8, 10, 3, _F64)] * 3, 16, 8, False),
    "two_geometries": ([(8, 10, 3, _F64)] * 2 + [(6, 9, 2, _F32)] * 3, 4, 1,
                       False),
    "numpy_pairs": ([(8, 10, 3, _F32)] * 4, 4, 1, True),
    # 40 streams pad to 64: two marshalling chunks, the second mostly padding
    "chunked_padded": ([(8, 10, 3, _F64)] * 40, 4, 2, False),
}


def _run(case):
    """Drive one service through every round of the case's traffic and
    return its stream ids and the service."""
    streams, events, depth, as_numpy = CASES[case]
    rng = np.random.default_rng(31)
    svc = SvdService(max_batch=1 << 30, method="direct")   # no autoflush
    ids = [f"s{i}" for i in range(len(streams))]
    for sid, (m, n, r, dt) in zip(ids, streams):
        svc.register(sid, _state(rng, m, n, r, dt))
    for _ in range(events):
        for sid, (m, n, r, dt) in zip(ids, streams):
            a, b = rng.normal(size=m), rng.normal(size=n)
            if not as_numpy:
                a, b = jnp.asarray(a, dt), jnp.asarray(b, dt)
            svc.enqueue(sid, a, b)
    rounds = 0
    while svc.pending():
        svc.flush_round(max_depth=depth)
        rounds += 1
    svc.drain()
    assert rounds >= 2
    return ids, svc


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiled_marshalling_bitwise_equals_eager(case, monkeypatch):
    """Several rounds through the compiled marshalling leave every stream
    state bitwise equal to the eager reference's."""
    ids, got = _run(case)
    with monkeypatch.context() as mp:
        mp.setattr(svd_service, "_assemble_round", _eager_assemble)
        mp.setattr(svd_service, "_split_round", _eager_split)
        _, want = _run(case)
    for sid in ids:
        for f in ("u", "s", "v"):
            x = np.asarray(getattr(got.state(sid), f))
            y = np.asarray(getattr(want.state(sid), f))
            assert x.dtype == y.dtype and x.shape == y.shape
            np.testing.assert_array_equal(x, y)


@pytest.fixture
def registry():
    prev = obs_metrics.set_registry(obs_metrics.MetricsRegistry())
    obs.enable()
    yield obs_metrics.registry()
    obs.disable()
    obs_metrics.set_registry(prev)


def _traces(reg, **labels) -> dict:
    counters = {d: reg.get("round_marshal_traces", direction=d, **labels)
                for d in ("assemble", "writeback")}
    return {d: c.value if c is not None else 0 for d, c in counters.items()}


def test_marshal_traces_count_new_round_shapes_only(registry):
    """One trace per direction for a round shape new to the process, none
    for a repeat; a group of another size that pads to the same batch
    reuses both executables."""
    m, n, r = 19, 17, 3            # a geometry no other test flushes
    rng = np.random.default_rng(3)
    svc = SvdService(max_batch=4, method="direct")
    svc._obs_labels = {"shard": "7"}
    ids = [f"s{i}" for i in range(4)]
    for sid in ids:
        svc.register(sid, _state(rng, m, n, r, jnp.float64))

    def round_of(live):
        for sid in ids[:live]:
            svc.enqueue(sid, jnp.asarray(rng.normal(size=m)),
                        jnp.asarray(rng.normal(size=n)))
        svc.flush()

    round_of(4)
    assert _traces(registry, shard="7") == {"assemble": 1, "writeback": 1}
    round_of(4)
    round_of(3)                    # 3 streams pad to the same batch of 4
    assert _traces(registry, shard="7") == {"assemble": 1, "writeback": 1}
    round_of(2)                    # a batch of 2: a new round shape
    assert _traces(registry, shard="7") == {"assemble": 2, "writeback": 2}
    svc.drain()


_RESTORE_SCRIPT = textwrap.dedent("""
    import json, sys
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np, jax.numpy as jnp
    from repro import obs
    from repro.core.svd_update import TruncatedSvd
    from repro.serve import SvdService

    mode, ckpt_dir = sys.argv[1:3]
    rng = np.random.default_rng(17)
    M, N, R, S = 8, 10, 3, 3

    def feed(svc, per_stream):
        for _ in range(per_stream):
            for i in range(S):
                svc.enqueue(f"s{i}", jnp.asarray(rng.normal(size=M)),
                            jnp.asarray(rng.normal(size=N)))

    def rounds(svc):
        # a padded depth-2 group (3 streams in a batch of 4), then a padded
        # depth-1 group
        feed(svc, 3)
        svc.flush_round(max_depth=2)
        svc.flush_round(max_depth=2)
        svc.drain()

    def traces():
        return {m.labels[0][1]: m.value for m in obs.registry().series()
                if m.name == "round_marshal_traces"}

    if mode == "save":
        svc = SvdService(max_batch=4)
        for i in range(S):
            svc.register(f"s{i}", TruncatedSvd(
                jnp.asarray(np.linalg.qr(rng.normal(size=(M, R)))[0]),
                jnp.asarray(np.sort(np.abs(rng.normal(size=R)))[::-1].copy()),
                jnp.asarray(np.linalg.qr(rng.normal(size=(N, R)))[0])))
        rounds(svc)
        svc.save(ckpt_dir, step=1)
        print(json.dumps({"warmed": [list(w) for w in svc.snapshot().warmed]}))
        sys.exit(0)

    obs.enable()
    step, svc = SvdService.restore(ckpt_dir)
    after_restore = traces()
    rounds(svc)
    print(json.dumps({"after_restore": after_restore,
                      "after_rounds": traces()}))
""")


def test_restored_service_traces_no_marshalling_in_first_rounds(tmp_path):
    """Restore builds both marshalling executables of every warmed round
    shape (padded ones included), so the first rounds of a restored service,
    in a fresh process, trace none."""
    env = {"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu", "HOME": "/tmp"}

    def run(mode):
        proc = subprocess.run(
            [sys.executable, "-c", _RESTORE_SCRIPT, mode, str(tmp_path)],
            capture_output=True, text=True, timeout=420, env=env)
        assert proc.returncode == 0, f"{mode} stderr:\n{proc.stderr[-4000:]}"
        return json.loads(proc.stdout.strip().splitlines()[-1])

    warmed = run("save")["warmed"]
    kinds = sorted(w[0] for w in warmed if w[0] != "trunc")
    assert kinds == ["trunc_batch", "trunc_scan2"]
    assert all(w[1] == 4 for w in warmed if w[0] != "trunc")
    got = run("resume")
    # one assembly per depth; the split sees the same (4, m, r) output at both
    assert got["after_restore"] == {"assemble": 2, "writeback": 1}
    assert got["after_rounds"] == got["after_restore"]
