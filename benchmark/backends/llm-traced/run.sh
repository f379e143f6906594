#!/bin/sh
# core/manager.py starts this as `/bin/sh run.sh --addr 127.0.0.1:<port>` with
# the server's environment; PYTHONPATH already leads to localai_tpu
exec "${BENCH_PYTHON:-python3}" "$(dirname "$0")/wrapper.py" "$@" --backend llm
