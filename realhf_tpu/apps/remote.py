"""Worker bootstrap entrypoint (reference ``realhf/apps/remote.py``):
the scheduler launches ``python -m realhf_tpu.apps.remote worker
--worker_type {master_worker|model_worker} --index I ...`` processes;
each runs its Worker poll loop until the controller sends exit.
"""

import argparse
import os


def main_worker(args):
    # The platform comes from JAX_PLATFORMS (CPU tests spawn workers
    # with JAX_PLATFORMS=cpu); each worker process owns its chips.
    from realhf_tpu.base.backend import enable_compile_cache
    enable_compile_cache()

    from realhf_tpu.base import cluster, logging, name_resolve
    from realhf_tpu.base.importing import import_usercode

    import_usercode()  # custom interfaces must register in workers too

    if os.environ.get("REALHF_TPU_NAME_RESOLVE_ROOT"):
        name_resolve.reconfigure(
            "nfs", record_root=os.environ["REALHF_TPU_NAME_RESOLVE_ROOT"])

    host = cluster.current_host_id()
    if host:
        # pod launch (system/pod.py): name the failure domain up front
        # so a host-grouped postmortem can match launcher/orchestrator
        # logs against worker boots
        logging.getLogger("remote").info(
            "Worker %s/%d booting on pod host %s (pid %d).",
            args.worker_type, args.index, host, os.getpid())

    if args.worker_type == "model_worker":
        from realhf_tpu.system.model_worker import ModelWorker
        cls = ModelWorker
        name = f"model_worker/{args.index}"
    elif args.worker_type == "master_worker":
        from realhf_tpu.system.master_worker import MasterWorker
        cls = MasterWorker
        name = "master_worker/0"
    elif args.worker_type == "gen_server":
        from realhf_tpu.serving.worker import GenServerWorker
        cls = GenServerWorker
        name = f"gen_server/{args.index}"
    elif args.worker_type == "router":
        from realhf_tpu.serving.worker import RouterWorker
        cls = RouterWorker
        name = f"router/{args.index}"
    elif args.worker_type == "gateway":
        from realhf_tpu.serving.worker import GatewayWorker
        cls = GatewayWorker
        name = f"gateway/{args.index}"
    else:
        raise ValueError(args.worker_type)
    cls(args.experiment_name, args.trial_name, name).run()


def main():
    parser = argparse.ArgumentParser("realhf_tpu remote entry")
    sub = parser.add_subparsers(dest="cmd", required=True)
    w = sub.add_parser("worker")
    w.add_argument("--worker_type", required=True,
                   choices=["model_worker", "master_worker",
                            "gen_server", "router", "gateway"])
    w.add_argument("--index", type=int, default=0)
    w.add_argument("--experiment_name", required=True)
    w.add_argument("--trial_name", required=True)
    args = parser.parse_args()
    if args.cmd == "worker":
        main_worker(args)


if __name__ == "__main__":
    main()
