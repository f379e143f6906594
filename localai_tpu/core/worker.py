"""Multi-host worker entrypoint — the `local-ai worker` role.

Reference parity: worker_llamacpp.go:66-92 starts an RPC server that lends
its devices to a master llama.cpp instance; grpc-server.cpp:256-278 registers
those remote devices. TPU-native version: every host joins one
jax.distributed job; the model is sharded over the GLOBAL mesh; rank 0 runs
the serving engine + gRPC backend; other ranks replay rank 0's dispatch
stream (parallel/distributed.py) so the SPMD programs stay in lockstep.

Topology flags mirror jax.distributed.initialize: --coordinator host:port,
--num-processes, --process-id. All ranks run the SAME command (different
--process-id), pointing at the SAME model directory.
"""
from __future__ import annotations

import logging

log = logging.getLogger("localai_tpu.worker")


def run_worker(args) -> int:
    from localai_tpu.parallel.distributed import (
        Follower, Replicator, init_distributed,
    )

    import os

    from localai_tpu.system.device import configure_compile_cache

    configure_compile_cache()
    init_distributed(args.coordinator, args.num_processes, args.process_id)
    import jax

    # topology truth comes from the initialized runtime, not the CLI — the
    # LOCALAI_* env path configures jax.distributed without any flags
    rank = jax.process_index()
    coordinator = args.coordinator or os.environ.get("LOCALAI_COORDINATOR")

    from localai_tpu.engine import Engine, EngineConfig
    from localai_tpu.engine.loader import (
        load_config, load_params, load_tokenizer,
    )
    from localai_tpu.models.llama import max_model_axis
    from localai_tpu.parallel.mesh import MeshConfig, build_mesh

    n_proc = jax.process_count()
    devices = jax.devices()
    cfg = load_config(args.model, dtype=args.dtype or None)
    if args.mesh_data or args.mesh_model:
        data = args.mesh_data or 1
        model = args.mesh_model or (len(devices) // data)
    else:
        model = max_model_axis(cfg, len(devices))
        data = len(devices) // model
    mesh = build_mesh(MeshConfig(data=data, model=model),
                      devices[: data * model])
    log.info("rank %d/%d: %d global devices, mesh data=%d model=%d",
             rank, n_proc, len(devices), data, model)

    params = load_params(args.model, cfg, dtype=args.dtype or None, mesh=mesh)
    tok = load_tokenizer(args.model)
    context = args.context_size or min(2048, cfg.max_position)
    chunk = min(512, context)
    buckets = tuple(b for b in (64, 256, 512) if b <= chunk) or (chunk,)

    replicator = None
    if rank == 0 and n_proc > 1:
        replicator = Replicator(args.replicate_port, n_proc - 1,
                                token=coordinator)

    eng = Engine(cfg, params, tok, EngineConfig(
        max_slots=args.parallel, max_context=context,
        prefill_buckets=buckets, prefill_chunk=chunk, mesh=mesh,
        replicator=replicator,
    ))

    if rank == 0:
        if replicator is not None:
            log.info("waiting for %d follower(s) on port %d...",
                     n_proc - 1, replicator.port)
            replicator.wait_for_followers()
        from localai_tpu.backend.llm import LLMServicer
        from localai_tpu.backend.server import serve_preloaded

        eng.start()
        servicer = LLMServicer(preloaded=(eng, cfg, tok, args.model))
        try:
            return serve_preloaded(args.addr, servicer)
        finally:
            if replicator is not None:
                replicator.close()
    else:
        host = (coordinator or "127.0.0.1").rsplit(":", 1)[0]
        chan = Follower(f"{host}:{args.replicate_port}", token=coordinator)
        log.info("rank %d following %s:%d", rank, host, args.replicate_port)
        eng.follow(chan)
        chan.close()
        return 0
