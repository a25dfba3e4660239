"""Open tensor files stay bounded: a merge of a checkpoint with many
tensors fits a low descriptor limit, and a reader's cap on open files
never hands a pread another tensor's descriptor.

A mixture-of-experts checkpoint stores hundreds of tensors per layer, one
file each; a reader that kept every file open would hold (K + 1) x tensors
descriptors through a merge of K experts.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.store import tensorstore
from repro.store.iostats import IOStats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the soft RLIMIT_NOFILE the merge runs under; 400 tensors x 5 models
#: held open at once would need 2,000 descriptors
FD_LIMIT = 256

_CHILD = r"""
import json, os, resource, sys
P = json.loads(sys.argv[1])
_, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
resource.setrlimit(resource.RLIMIT_NOFILE, (P["fd_limit"], hard))

import numpy as np

peak = [0]
_os_open = os.open


def counting_open(*args, **kwargs):
    fd = _os_open(*args, **kwargs)
    try:
        peak[0] = max(peak[0], len(os.listdir("/proc/self/fd")))
    except OSError:  # at the limit: leave the failure to the program
        peak[0] = P["fd_limit"]
    return fd


os.open = counting_open

from ml_dtypes import bfloat16
from repro.api import MergeSpec, Session
from repro.core.executor import PipelineConfig

rng = np.random.default_rng(11)
# values on a grid of 2**-6 with few significant bits, and lam a power of
# two: every sum, difference and product is exact in float32 and
# bfloat16 (TIES's one division rounds far from any bfloat16 tie), so
# the jax kernel's and the stream engine's arithmetic give the same bytes
shapes = [(64, 64), (48, 40), (96,)]
base = {"t%03d.weight" % i: (rng.integers(-64, 65, size=shapes[i % 3])
                             / 64.0).astype(bfloat16)
        for i in range(P["tensors"])}
sess = Session(P["workspace"], block_size=4096)
sess.register_model("base", base)
ids = []
for e in range(P["k"]):
    sess.register_model("e%d" % e, {
        n: (v.astype(np.float32)
            + rng.integers(-4, 5, size=v.shape) / 64.0).astype(bfloat16)
        for n, v in base.items()})
    ids.append("e%d" % e)
sess.ensure_analyzed("base", ids)

out = {}
for sid, kw in (("device", {"pipeline": PipelineConfig(kernel="jax")}),
                ("stream", {"compute": "stream"})):
    sess.submit(MergeSpec.build("base", ids, **P["spec"]), sid=sid)
    (res,) = sess.run_all(**kw)
    out[sid] = sess.load(sid)
    if sid == "device":
        backend = res.stats["pipeline"]["backend"]
        fd_opens = res.stats["trace"]["counters"].get("fd_opens", 0)
sess.close()
differ = [n for n in base if out["device"][n].tobytes()
          != out["stream"][n].tobytes()]
print(json.dumps({"peak_fds": peak[0], "tensors": len(out["device"]),
                  "differ": differ, "backend": backend,
                  "fd_opens": fd_opens}))
"""


@pytest.mark.parametrize("spec", [
    {"op": "ta", "theta": {"lam": 0.5}},
    {"op": "ties", "theta": {"trim_frac": 0.2, "lam": 1.0}, "budget": "50%"},
], ids=["ta", "ties-b50"])
def test_many_tensor_merge_fits_a_low_descriptor_limit(tmp_path, spec):
    n_tensors, k = 400, 4
    params = {"fd_limit": FD_LIMIT, "tensors": n_tensors, "k": k,
              "workspace": str(tmp_path / "ws"), "spec": spec}
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", _CHILD, json.dumps(params)],
                          env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["tensors"] == n_tensors
    assert got["differ"] == []
    assert got["backend"] == "jnp-cpu"
    assert got["peak_fds"] < FD_LIMIT
    # each model's tensor files are opened about once, not once per block
    assert 0 < got["fd_opens"] <= (k + 1) * n_tensors


def _model(root, n_tensors):
    """A stored model whose tensor ``i`` holds only the value ``i``."""
    store = tensorstore.CheckpointStore(str(root), IOStats())
    store.write_model("m", {"t%02d" % i: np.full(1024, i, np.int32)
                            for i in range(n_tensors)})
    return store


def test_threads_read_the_right_tensor_under_a_small_cap(tmp_path,
                                                         monkeypatch):
    n_tensors, n_threads, reads = 40, 8, 400
    monkeypatch.setattr(tensorstore, "MAX_OPEN_TENSOR_FILES", 2)
    pread = os.pread

    def yielding_pread(fd, n, offset):
        # other threads pin, open and close files between a reader's
        # choice of descriptor and its read
        time.sleep(0)
        return pread(fd, n, offset)

    monkeypatch.setattr(os, "pread", yielding_pread)
    store = _model(tmp_path, n_tensors)
    reader = store.open_model("m")
    wrong, errors, most_open = [], [], [0]

    def work(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(reads):
                i = int(rng.integers(n_tensors))
                off = 4 * int(rng.integers(1000))
                data = reader.read_range("t%02d" % i, off, 96, "base")
                vals = np.frombuffer(data, np.int32)
                if not (vals == i).all():
                    wrong.append((i, sorted(set(vals.tolist()))))
                most_open[0] = max(most_open[0], len(reader._fds))
        except OSError as e:  # a pread on a descriptor closed under it
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(s,))
                   for s in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors and not wrong, (errors[:3], wrong[:3])
    # beyond the cap, only files that preads had pinned at that moment
    assert most_open[0] <= 2 + n_threads
    assert len(reader._fds) <= 2
    reader.close()
    assert not reader._fds


def test_a_reader_opens_each_file_once_when_read_in_order(tmp_path):
    from repro.core import trace

    store = _model(tmp_path, 30)
    rec = trace.Recorder("job")
    with store.open_model("m") as reader, rec.span(trace.BG_STAGE):
        for i in range(30):
            for off in (0, 1024, 2048):
                reader.read_range("t%02d" % i, off, 1024, "base")
            assert len(reader._fds) <= tensorstore.MAX_OPEN_TENSOR_FILES
    assert rec.summary()["counters"]["fd_opens"] == 30
