"""`python -m localai_tpu.backend --addr 127.0.0.1:PORT --backend llm`"""
import argparse
import sys

from localai_tpu.backend.server import ROLES, serve_blocking


def main(argv=None):
    from localai_tpu.system.device import configure_compile_cache

    # before any role imports jax: this process owns whatever device
    # JAX_PLATFORMS gives it, and its compiles should outlive it
    configure_compile_cache()
    p = argparse.ArgumentParser(prog="localai_tpu.backend")
    p.add_argument("--addr", default="127.0.0.1:50051")
    p.add_argument("--backend", default="llm", choices=sorted(ROLES))
    args = p.parse_args(argv)
    # chaos-harness spawn faults (localai_tpu/testing/faults.py): crash
    # before binding (the dead-child / port-TOCTOU shape the manager must
    # detect fast) or stall before health (slow-start)
    from localai_tpu.testing import faults

    arg = faults.fire("spawn_crash")
    if arg is not None:
        sys.exit(int(arg) or 3)
    arg = faults.fire("slow_start")
    if arg:
        import time

        time.sleep(arg)
    return serve_blocking(addr=args.addr, backend=args.backend)


if __name__ == "__main__":
    sys.exit(main())
