"""tpu-vit's chip benchmark. ``python benchmark/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``; see PERF.md."""
