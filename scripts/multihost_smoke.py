"""Multi-process multi-host smoke + failure-recovery test on CPU.

Reference parity: the reference proves its distributed plane without a
cluster by running Spark `local[N]` (SURVEY.md §4 "Distributed-without-
a-cluster"); the TPU-native equivalent is N real `jax.distributed`
processes × M virtual CPU devices each — the same code path a v5e pod
runs (PJRT process group, global mesh, cross-process collectives),
minus the ICI.

Leg 1 (smoke): 2 procs × 4 devices, DP/ZeRO-1 training through
Optimizer.set_mesh → DistriOptimizer with per-host sharded data,
checkpoint + in-process resume, digests identical across processes.

Leg 2 (kill/resume — SURVEY §5.3, reference anchor DistriOptimizer
retry/getLatestFile): 4 procs × 2 devices. An uninterrupted 12-step
run records a sha256 parameter digest; a second run is SIGKILLed
mid-training (one worker first — the pod failure model: one host dies,
the synchronous collective wedges the rest, the launcher reaps the
job), then ALL processes restart with --resume and reload the latest
atomic checkpoint. Digests must be bit-identical to the uninterrupted
run on every process.

Leg 3 (ckpt_corrupt — ISSUE 1 verified checkpoint integrity): same
4×2 job, killed once checkpoint-6 publishes; the newest checkpoint's
model.npz is truncated on disk before the restart. Every process must
detect the damage (per-array checksums, serialization/checkpoint.py),
fall back to the newest VALID checkpoint, and finish bit-identical to
the uninterrupted run.

Leg 4 (zero2_resume — ISSUE 9 preemption-tolerant training plane):
2 procs × 4 devices with `set_mesh(zero=2)` (master fp32 weights
sharded across all 8 devices) and `set_checkpoint(sharded=True,
async_save=True)` — each host background-writes ONLY the shard units
its devices own, host 0 publishes MANIFEST.json last. One worker is
SIGKILLed after the first sharded checkpoint publishes (the
collective wedges, the launcher reaps the job — a preempted-host
model with possibly-torn in-flight saves on disk); the full restart
with --resume must reshard the published checkpoint and finish
bit-identical to the uninterrupted zero2 run.

    python scripts/multihost_smoke.py          # all legs

Process model: the PARENT never imports jax — it only spawns and reaps.
Every child pins itself to CPU (`JAX_PLATFORMS=cpu` + virtual devices)
before its own first `import jax`, inside `child()`. So this script
never takes an accelerator, and never holds one while a child needs
it; keep both properties when editing.
"""

import argparse
import json
import os
import subprocess
import sys

NUM_PROCESSES = 2
DEVICES_PER_PROC = 4
PORT = 12000 + (os.getpid() % 2000)  # avoid collisions across runs


def child(args):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count="
                               f"{args.devices_per_proc}")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    # the product bring-up path (utils/Engine.scala#Engine.init parity):
    # BIGDL_* env vars are what scripts/launch_pod.sh exports
    os.environ["BIGDL_COORDINATOR"] = f"localhost:{args.port}"
    os.environ["BIGDL_NUM_PROCESSES"] = str(args.num_processes)
    os.environ["BIGDL_PROCESS_ID"] = str(args.process_id)
    from bigdl_tpu.utils.engine import Engine

    Engine.init_distributed()
    assert jax.process_count() == args.num_processes, jax.process_count()
    assert jax.device_count() == (args.num_processes
                                  * args.devices_per_proc)

    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.optim import Adam, Optimizer, Trigger, Loss
    from bigdl_tpu.parallel import make_mesh

    rng = np.random.RandomState(0)  # same data on every host, sharded below
    X = (rng.randn(128, 8).astype(np.float32) +
         np.repeat(np.eye(4, 8) * 3, 32, 0).astype(np.float32))
    Y = np.repeat(np.arange(4), 32)
    elements = [Sample(X[i], int(Y[i])) for i in range(128)]
    dataset = DataSet.sharded(elements, seed=3)      # per-process shard
    # 33 samples -> shards of 17 and 16: with local batches of 16 one
    # host runs 2 eval rounds, the other 1 — exercises the uneven-shard
    # equalization in DistriOptimizer._validate_mesh (no deadlock)
    val = DataSet.sharded(elements[:33], seed=3)

    def build():
        return nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                             nn.Linear(16, 4),
                             nn.LogSoftMax()).build(jax.random.PRNGKey(0))

    mesh = make_mesh({"data": jax.device_count()})
    ckpt = os.path.join(args.workdir, "ckpt")

    def train(end_iter, resume):
        opt = (Optimizer(build(), dataset, nn.ClassNLLCriterion(),
                         batch_size=32)                # GLOBAL batch
               .set_optim_method(Adam(learningrate=1e-2))
               .set_gradient_accumulation(2)
               .set_end_when(Trigger.max_iteration(end_iter))
               .set_validation(Trigger.several_iteration(3), val,
                               [Loss(nn.ClassNLLCriterion())], 32)
               .set_checkpoint(ckpt, Trigger.several_iteration(3),
                               sharded=args.zero2, async_save=args.zero2)
               .set_mesh(mesh, zero=2 if args.zero2 else 1))
        if resume:
            opt.resume_from_checkpoint()
        return opt.optimize(), opt

    if args.leg == "smoke":
        m1, _ = train(3, resume=False)   # 3 steps + checkpoint
        m2, opt = train(6, resume=True)  # resume, 3 more steps
    else:  # kill_resume: one uninterrupted (or resumed) run to the end
        m2, opt = train(args.end_iter, resume=args.resume)

    flat = np.concatenate([np.ravel(np.asarray(a, np.float32))
                           for _, a in m2.parameters()])
    assert np.isfinite(flat).all(), "non-finite parameters"

    # parameters must be IDENTICAL across processes (replicated plane):
    # compare digests via the filesystem. sha256 of the raw bytes is the
    # bit-identity check; the float sum stays for human logs.
    import hashlib

    digest = float(np.sum(np.abs(flat)))
    sha = hashlib.sha256(flat.tobytes()).hexdigest()
    out = {"process_id": args.process_id, "digest": digest,
           "sha256": sha,
           "processes": jax.process_count(),
           "devices": jax.device_count(),
           "checkpoint_resumed": args.leg == "smoke" or args.resume,
           # recovery provenance for the ckpt_corrupt leg: which dir
           # the resume actually loaded, and which it skipped as
           # corrupt (serialization/checkpoint.py fallback)
           "resumed_from": os.path.basename(
               opt.checkpoint._last_loaded or "") if args.resume else None,
           "corrupt_skipped": [os.path.basename(d) for d
                               in opt.checkpoint.corrupt_skipped]}
    with open(os.path.join(args.workdir, f"proc{args.process_id}.json"),
              "w") as f:
        json.dump(out, f)
    print(f"[proc {args.process_id}] OK digest={digest:.6f} sha={sha[:12]}")


def _spawn_group(leg, n_procs, devices_per_proc, port, workdir,
                 end_iter=6, resume=False, zero2=False):
    procs = []
    for pid in range(n_procs):
        cmd = [sys.executable, os.path.abspath(__file__),
               "--process-id", str(pid), "--num-processes", str(n_procs),
               "--devices-per-proc", str(devices_per_proc),
               "--port", str(port), "--workdir", workdir,
               "--leg", leg, "--end-iter", str(end_iter)]
        if resume:
            cmd.append("--resume")
        if zero2:
            cmd.append("--zero2")
        procs.append(subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    return procs


def _reap(procs, timeout=420):
    try:
        outs = [p.communicate(timeout=timeout)[0].decode() for p in procs]
    except subprocess.TimeoutExpired:
        # a hung child must not leak (it holds the coordinator port)
        for p in procs:
            p.kill()
        outs = [p.communicate()[0].decode() for p in procs]
    codes = [p.returncode for p in procs]
    for pid, (c, o) in enumerate(zip(codes, outs)):
        if c != 0:
            print(f"--- proc {pid} (rc={c}) ---\n{o[-2000:]}")
    return codes


def _collect(workdir, n_procs):
    digests, shas = [], []
    for pid in range(n_procs):
        with open(os.path.join(workdir, f"proc{pid}.json")) as f:
            d = json.load(f)
        digests.append(d["digest"])
        shas.append(d["sha256"])
    return digests, shas


def _leg_smoke(port):
    import tempfile

    workdir = tempfile.mkdtemp(prefix="multihost_smoke_")
    procs = _spawn_group("smoke", NUM_PROCESSES, DEVICES_PER_PROC, port,
                         workdir)
    codes = _reap(procs)
    ok = all(c == 0 for c in codes)
    digests = []
    if ok:
        digests, _ = _collect(workdir, NUM_PROCESSES)
        ok = len(set(digests)) == 1
    return {"ok": ok, "processes": NUM_PROCESSES,
            "devices_per_process": DEVICES_PER_PROC,
            "return_codes": codes, "digests": digests,
            "steps": 6, "grad_accum": 2, "checkpoint_resume": True}


def _leg_kill_resume(port):
    """4-process job, one worker SIGKILLed mid-training, full restart
    with --resume: parameter sha256 must equal the uninterrupted run's
    on every process."""
    import tempfile
    import time

    n, dpp, end = 4, 2, 12
    # uninterrupted reference run
    wd_ref = tempfile.mkdtemp(prefix="multihost_ref_")
    codes_ref = _reap(_spawn_group("kill_resume", n, dpp, port, wd_ref,
                                   end_iter=end))
    if any(c != 0 for c in codes_ref):
        return {"ok": False, "stage": "reference", "return_codes": codes_ref}
    _, shas_ref = _collect(wd_ref, n)

    # interrupted run: kill worker 2 as soon as the FIRST checkpoint
    # (checkpoint-3 of 12 steps) is published — earliest point where a
    # resume is possible, widest remaining-training window for the kill
    # to land mid-run. Poll fast: the whole CPU job takes seconds.
    wd = tempfile.mkdtemp(prefix="multihost_kill_")
    procs = _spawn_group("kill_resume", n, dpp, port + 1, wd,
                         end_iter=end)
    ckdir = os.path.join(wd, "ckpt")
    marker = os.path.join(ckdir, "checkpoint-3")
    deadline = time.time() + 300
    saw_ckpt = False
    while time.time() < deadline:
        if os.path.isdir(marker):
            saw_ckpt = True
            break
        if any(p.poll() is not None for p in procs):
            break  # someone already exited — fail below
        time.sleep(0.05)
    killed_mid_training = False
    latest_at_kill = None
    if saw_ckpt and all(p.poll() is None for p in procs):
        procs[2].kill()              # the dying host
        killed_mid_training = True
        import re
        published = [d for d in os.listdir(ckdir)
                     if re.fullmatch(r"checkpoint-(\d+)", d)]
        latest_at_kill = max(published,
                             key=lambda d: int(d.split("-")[1]))
        time.sleep(5)                # collective wedges; reap the job
    for p in procs:
        if p.poll() is None:
            p.kill()
    _reap(procs, timeout=30)
    if not killed_mid_training:
        return {"ok": False, "stage": "kill",
                "detail": "training finished (or a worker exited) before "
                          "the kill could land after checkpoint-3 — "
                          "no mid-training recovery was exercised"}

    # full restart with --resume: reload latest checkpoint, finish
    codes_res = _reap(_spawn_group("kill_resume", n, dpp, port + 2, wd,
                                   end_iter=end, resume=True))
    if any(c != 0 for c in codes_res):
        return {"ok": False, "stage": "resume", "return_codes": codes_res}
    _, shas_res = _collect(wd, n)

    ok = (len(set(shas_res)) == 1 and len(set(shas_ref)) == 1
          and shas_res[0] == shas_ref[0])
    return {"ok": ok, "processes": n, "devices_per_process": dpp,
            "steps": end, "killed_process": 2,
            "latest_checkpoint_at_kill": latest_at_kill,
            "sha256_uninterrupted": shas_ref[0][:16],
            "sha256_resumed": shas_res[0][:16],
            "bit_identical": ok}


def _leg_ckpt_corrupt(port):
    """kill_resume variant for checkpoint INTEGRITY (ISSUE 1): the whole
    job is killed once checkpoint-6 publishes, the newest checkpoint's
    model arrays are truncated on disk (torn flush / bit rot), and the
    restart must detect the damage (per-array checksums + zip
    structure), fall back to the newest VALID checkpoint on every
    process, and still finish bit-identical to the uninterrupted run."""
    import re
    import tempfile
    import time

    n, dpp, end = 4, 2, 12
    wd_ref = tempfile.mkdtemp(prefix="multihost_ckref_")
    codes_ref = _reap(_spawn_group("kill_resume", n, dpp, port, wd_ref,
                                   end_iter=end))
    if any(c != 0 for c in codes_ref):
        return {"ok": False, "stage": "reference", "return_codes": codes_ref}
    _, shas_ref = _collect(wd_ref, n)

    # run until two checkpoints exist (3 and 6), then kill the job
    wd = tempfile.mkdtemp(prefix="multihost_ckcorrupt_")
    procs = _spawn_group("kill_resume", n, dpp, port + 1, wd,
                         end_iter=end)
    ckdir = os.path.join(wd, "ckpt")
    marker = os.path.join(ckdir, "checkpoint-6")
    deadline = time.time() + 300
    saw = False
    while time.time() < deadline:
        if os.path.isdir(marker):
            saw = True
            break
        if any(p.poll() is not None for p in procs):
            break
        time.sleep(0.05)
    for p in procs:
        p.kill()
    _reap(procs, timeout=30)
    if not saw:
        return {"ok": False, "stage": "kill",
                "detail": "checkpoint-6 never appeared (or a worker "
                          "exited first) — nothing to corrupt"}

    # truncate the newest published checkpoint's model arrays (inline —
    # the launcher stays free of jax imports; same damage model as
    # utils.faults.corrupt_file 'truncate')
    published = sorted(
        (d for d in os.listdir(ckdir)
         if re.fullmatch(r"checkpoint-(\d+)", d)),
        key=lambda d: int(d.split("-")[1]))
    newest, expect_fallback = published[-1], published[-2]
    npz = os.path.join(ckdir, newest, "model.npz")
    size = os.path.getsize(npz)
    with open(npz, "r+b") as f:
        f.truncate(max(size // 2, 1))

    codes_res = _reap(_spawn_group("kill_resume", n, dpp, port + 2, wd,
                                   end_iter=end, resume=True))
    if any(c != 0 for c in codes_res):
        return {"ok": False, "stage": "resume", "return_codes": codes_res}
    _, shas_res = _collect(wd, n)
    resumed_from, skipped = [], []
    for pid in range(n):
        with open(os.path.join(wd, f"proc{pid}.json")) as f:
            d = json.load(f)
        resumed_from.append(d.get("resumed_from"))
        skipped.append(d.get("corrupt_skipped", []))
    fell_back = (all(r == expect_fallback for r in resumed_from)
                 and all(newest in s for s in skipped))
    ok = (fell_back and len(set(shas_res)) == 1
          and len(set(shas_ref)) == 1 and shas_res[0] == shas_ref[0])
    return {"ok": ok, "processes": n, "devices_per_process": dpp,
            "steps": end, "corrupted": newest,
            "resumed_from": resumed_from[0],
            "fell_back_on_every_process": fell_back,
            "sha256_uninterrupted": shas_ref[0][:16],
            "sha256_resumed": shas_res[0][:16],
            "bit_identical": shas_res[0] == shas_ref[0]}


def _leg_zero2_resume(port):
    """ISSUE 9: kill/resume over the FULL elastic-training plane —
    ZeRO-2 weight sharding across both hosts' devices, each host
    background-writing only its own shard units, manifest-last
    publish. One worker SIGKILLed after the first sharded checkpoint
    publishes; full restart with --resume must finish bit-identical
    to the uninterrupted zero2 run."""
    import re
    import tempfile
    import time

    n, dpp, end = 2, 4, 12
    wd_ref = tempfile.mkdtemp(prefix="multihost_z2ref_")
    codes_ref = _reap(_spawn_group("kill_resume", n, dpp, port, wd_ref,
                                   end_iter=end, zero2=True))
    if any(c != 0 for c in codes_ref):
        return {"ok": False, "stage": "reference",
                "return_codes": codes_ref}
    _, shas_ref = _collect(wd_ref, n)

    wd = tempfile.mkdtemp(prefix="multihost_z2kill_")
    procs = _spawn_group("kill_resume", n, dpp, port + 1, wd,
                         end_iter=end, zero2=True)
    ckdir = os.path.join(wd, "ckpt")
    # a sharded checkpoint only EXISTS once MANIFEST.json lands (the
    # manifest-last publish point) — the dir alone is a torn save
    marker = os.path.join(ckdir, "checkpoint-3", "MANIFEST.json")
    deadline = time.time() + 300
    saw_ckpt = False
    while time.time() < deadline:
        if os.path.exists(marker):
            saw_ckpt = True
            break
        if any(p.poll() is not None for p in procs):
            break
        time.sleep(0.05)
    killed = False
    if saw_ckpt and all(p.poll() is None for p in procs):
        procs[1].kill()              # the preempted host
        killed = True
        time.sleep(5)                # collective wedges; reap the job
    for p in procs:
        if p.poll() is None:
            p.kill()
    _reap(procs, timeout=30)
    if not killed:
        return {"ok": False, "stage": "kill",
                "detail": "no published sharded checkpoint before the "
                          "job ended — nothing to resume from"}
    published = [d for d in os.listdir(ckdir)
                 if re.fullmatch(r"checkpoint-(\d+)", d)
                 and os.path.exists(os.path.join(ckdir, d,
                                                 "MANIFEST.json"))]
    shard_units = [f for f in os.listdir(os.path.join(
        ckdir, "checkpoint-3")) if f.startswith("optim-shard")
        and f.endswith(".npz")]

    codes_res = _reap(_spawn_group("kill_resume", n, dpp, port + 2, wd,
                                   end_iter=end, resume=True,
                                   zero2=True))
    if any(c != 0 for c in codes_res):
        return {"ok": False, "stage": "resume",
                "return_codes": codes_res}
    _, shas_res = _collect(wd, n)
    ok = (len(set(shas_res)) == 1 and len(set(shas_ref)) == 1
          and shas_res[0] == shas_ref[0] and len(shard_units) == 8)
    return {"ok": ok, "processes": n, "devices_per_process": dpp,
            "steps": end, "zero": 2, "killed_process": 1,
            "sharded_checkpoints_at_kill": sorted(published),
            "shard_units_in_first_ckpt": len(shard_units),
            "sha256_uninterrupted": shas_ref[0][:16],
            "sha256_resumed": shas_res[0][:16],
            "bit_identical": shas_res[0] == shas_ref[0]}


def launcher(legs):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "MULTIHOST.json")
    # merge-preserving: running a subset of legs keeps the other legs'
    # last recorded results in the artifact
    result = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                result = json.load(f)
        except Exception:
            result = {}
    ok = True
    if "smoke" in legs:
        smoke = _leg_smoke(PORT)
        prev = {k: result[k] for k in ("kill_resume", "ckpt_corrupt",
                                       "zero2_resume")
                if k in result}
        result = dict(smoke)  # legacy top-level shape for leg 1
        result.update(prev)
        ok = ok and smoke["ok"]
    if "kill_resume" in legs:
        kill = _leg_kill_resume(PORT + 10)
        result["kill_resume"] = kill
        ok = ok and kill.get("ok", False)
    if "ckpt_corrupt" in legs:
        corrupt = _leg_ckpt_corrupt(PORT + 20)
        result["ckpt_corrupt"] = corrupt
        ok = ok and corrupt.get("ok", False)
    if "zero2_resume" in legs:
        z2 = _leg_zero2_resume(PORT + 30)
        result["zero2_resume"] = z2
        ok = ok and z2.get("ok", False)
    result["ok"] = bool(ok and result.get("ok", True))
    with open(path, "w") as f:
        json.dump(result, f)
    print(json.dumps(result))
    sys.exit(0 if ok else 1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--num-processes", type=int, default=NUM_PROCESSES)
    ap.add_argument("--devices-per-proc", type=int,
                    default=DEVICES_PER_PROC)
    ap.add_argument("--port", type=int, default=PORT)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--leg", default="smoke",
                    choices=["smoke", "kill_resume"])
    ap.add_argument("--legs",
                    default="smoke,kill_resume,ckpt_corrupt,zero2_resume",
                    help="launcher mode: comma subset of legs to run")
    ap.add_argument("--end-iter", type=int, default=6)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--zero2", action="store_true",
                    help="child mode: ZeRO-2 weight sharding + sharded "
                         "async checkpoints (ISSUE 9)")
    args = ap.parse_args()
    if args.process_id is None:
        launcher(set(args.legs.split(",")))
    else:
        child(args)


if __name__ == "__main__":
    main()
