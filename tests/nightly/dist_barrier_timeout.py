"""Multi-process barrier-timeout attribution script: rank 1 deliberately
NEVER enters the kvstore barrier; rank 0, with
MXNET_KVSTORE_BARRIER_TIMEOUT set, must abort with a typed
`BarrierTimeout` that NAMES rank 1 as the missing peer (arrival
announcements travel through the jax.distributed coordinator KV store).

Launched by tools/launch.py (the reference's `--launcher local` pattern):

    PYTHONPATH= python tools/launch.py -n 2 --env JAX_PLATFORMS=cpu \
        python tests/nightly/dist_barrier_timeout.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, os.pardir))


def main():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.kvstore import BarrierTimeout

    parallel.initialize()
    rank, world = parallel.rank(), parallel.world_size()
    assert world == 2, "run under tools/launch.py -n 2"

    kv = mx.kv.create("dist_sync")

    # warmup barrier: both ranks participate, must complete well inside
    # the timeout (proves the timeout path doesn't false-positive)
    os.environ["MXNET_KVSTORE_BARRIER_TIMEOUT"] = "60"
    kv.barrier()

    if rank == 1:
        # the "dead" peer: skip barrier #2 entirely and exit cleanly —
        # rank 0 must time out and attribute the stall to us
        print("barrier timeout peer-skip OK", flush=True)
        return 0

    os.environ["MXNET_KVSTORE_BARRIER_TIMEOUT"] = "6"
    try:
        kv.barrier()
    except BarrierTimeout as e:
        assert "timed out" in str(e), e
        # attribution: the coordinator KV store must name rank 1 (an
        # empty list would mean the announce/try_get path regressed)
        assert e.missing_ranks == [1], \
            f"expected missing_ranks [1], got {e.missing_ranks}: {e}"
        print("barrier timeout peer-skip OK", flush=True)
        # leave the way a torn-down job does. The watcher thread is still
        # inside the abandoned rendezvous, and CPython ends such a daemon
        # thread at finalization with pthread_exit, which aborts inside
        # the C++ collective ("FATAL: exception not rethrown", rc 250).
        # So: disconnect from the coordinator first (rank 1's own
        # shutdown waits for this one), then exit without finalizing.
        import jax
        jax.distributed.shutdown()
        os._exit(0)
    raise AssertionError("barrier with an absent peer did not time out")


if __name__ == "__main__":
    sys.exit(main())
