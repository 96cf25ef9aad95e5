#!/usr/bin/env python
"""Single-host multi-process smoke test (``src/tools/cluster_test.sh`` parity).

The reference's operational check launched master + server + worker with
nohup on one box and watched master.log. Here the three roles are one SPMD
``train`` role; the smoke test spawns N processes that rendezvous through the
JAX coordination service (the master-equivalent), run a tiny distributed
word2vec job on CPU devices, hit the end-of-training barrier, and exit 0.

    python tools/cluster_test.py --nproc 2

This is a CPU test: every child is started with ``JAX_PLATFORMS=cpu`` (set
below), so it never asks for a chip — a chip belongs to one process, and
this launcher imports no jax backend itself. It says nothing about TPUs.

Each process logs to ``/tmp/snails_cluster_test/proc<i>.log`` (the master.log
analog).
"""

import argparse
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import os, sys
import jax  # JAX_PLATFORMS=cpu comes from the launcher's env
import numpy as np

pid = int(sys.argv[1]); nproc = int(sys.argv[2]); port = sys.argv[3]

from swiftsnails_tpu.parallel.cluster import barrier, initialize_cluster, process_info
from swiftsnails_tpu.utils.config import Config

cfg = Config({
    "master_addr": "127.0.0.1:" + port,
    "expected_node_num": str(nproc),
    "init_timeout": "60",
})
initialize_cluster(cfg, process_id=pid)
idx, count = process_info()
assert count == nproc, (idx, count)
print(f"process {idx}/{count} joined", flush=True)

# Every process sees the same logical corpus (seed 0) and trains on ITS
# contiguous span — the reference's Hadoop stdin-split contract
# (run_worker.sh: `cat > ./data.txt`), here via shard_token_stream.
from swiftsnails_tpu.data.vocab import Vocab
from swiftsnails_tpu.framework.trainer import TrainLoop
from swiftsnails_tpu.models.word2vec import Word2VecTrainer
from swiftsnails_tpu.parallel.cluster import shard_token_stream

rng = np.random.default_rng(0)
vocab = Vocab([f"w{i}" for i in range(32)],
              np.maximum(rng.integers(1, 9, 32), 1).astype(np.int64))
full = rng.integers(0, 32, 2000).astype(np.int32)
corpus = shard_token_stream(full)
# spans are np.array_split slices: disjoint, contiguous, covering the corpus
expect = np.array_split(full, nproc)[idx]
assert np.array_equal(corpus, expect), "wrong shard for this process"
print(f"process {idx} shard: tokens [{sum(len(s) for s in np.array_split(full, nproc)[:idx])}, +{len(corpus)})", flush=True)
tcfg = Config({"dim": "8", "window": "2", "negatives": "2",
               "learning_rate": "0.1", "batch_size": "64", "subsample": "0",
               "num_iters": "1", "use_native": "0"})
tr = Word2VecTrainer(tcfg, mesh=None, corpus_ids=corpus, vocab=vocab)
TrainLoop(tr, log_every=0).run(max_steps=5)
barrier("end_of_training")
print(f"process {idx} done", flush=True)
"""


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--nproc", type=int, default=2)
    p.add_argument("--port", default="29517")
    p.add_argument("--logdir", default="/tmp/snails_cluster_test")
    args = p.parse_args(argv)

    os.makedirs(args.logdir, exist_ok=True)
    script = os.path.join(args.logdir, "child.py")
    with open(script, "w") as f:
        f.write(_CHILD)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = []
    logs = []
    for i in range(args.nproc):
        log = open(os.path.join(args.logdir, f"proc{i}.log"), "w")
        logs.append(log)
        procs.append(
            subprocess.Popen(
                [sys.executable, script, str(i), str(args.nproc), args.port],
                stdout=log, stderr=subprocess.STDOUT, env=env, cwd=REPO,
            )
        )
    deadline = time.time() + 300
    rc = 0
    for i, proc in enumerate(procs):
        remaining = max(1, deadline - time.time())
        try:
            code = proc.wait(timeout=remaining)
        except subprocess.TimeoutExpired:
            proc.kill()
            code = -9
        if code != 0:
            rc = 1
            print(f"process {i} FAILED (exit {code}); log:", file=sys.stderr)
            sys.stderr.write(
                open(os.path.join(args.logdir, f"proc{i}.log")).read()
            )
    for log in logs:
        log.close()
    print("cluster smoke test:", "PASS" if rc == 0 else "FAIL")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
