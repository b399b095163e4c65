"""Quickstart: submit jobs to FfDL and watch them run.

    PYTHONPATH=src python examples/quickstart.py

Shows the user-facing surface of the platform (FfDL §3.1): a manifest is
"code + data location + resources"; the platform does the rest — placement,
status pipeline, logs, results.
"""

from repro.api import ApiClient
from repro.core import FfDLPlatform, JobManifest, JobStatus


def main():
    # a small cluster: 4 hosts x 4 chips
    platform = FfDLPlatform(n_hosts=4, chips_per_host=4, placement="pack")
    platform.admission.register_tenant("demo-team", quota_chips=12)
    # every user-facing call goes through the v1 API tier with a
    # tenant-scoped key (the raw platform facade is gone)
    client = ApiClient.for_platform(platform, tenant="demo-team")

    # 1) a simulated job (what the scheduling benchmarks use)
    sim = client.submit(JobManifest(
        name="preprocessing-sim", tenant="demo-team",
        n_learners=2, chips_per_learner=2, sim_duration=120))

    # 2) a real JAX training job: tiny llama-family model, 40 steps
    train = client.submit(JobManifest(
        name="smollm-tiny-train", tenant="demo-team",
        n_learners=1, chips_per_learner=2,
        arch="smollm-360m", checkpoint_interval=20,
        train={"steps": 40, "batch": 4, "seq": 64, "lr": 1e-3}))

    print(f"submitted: {sim} (simulated), {train} (real training)")
    last = {}
    while True:
        platform.tick()
        for j in (sim, train):
            st = client.status(j)
            if last.get(j) != st:
                rec = platform.meta.get(j)
                print(f"[t={platform.clock.now():7.1f}s] {j} "
                      f"{st.value:12s} step={rec.progress_step}")
                last[j] = st
        if all(client.status(j) in (JobStatus.COMPLETED, JobStatus.FAILED)
               for j in (sim, train)):
            break

    print("\nstatus history of the training job:")
    for ts, status, msg in client.status_history(train):
        print(f"  {ts:8.1f}s  {status:12s} {msg}")

    print(f"\ncluster utilization now: {platform.cluster.utilization():.0%}")
    print(f"results in object store: "
          f"{platform.objstore.list('results', train)[:3]} ...")
    failed = [j for j in (sim, train)
              if client.status(j) != JobStatus.COMPLETED]
    if failed:
        raise SystemExit(f"jobs did not complete: {failed}")


if __name__ == "__main__":
    main()
