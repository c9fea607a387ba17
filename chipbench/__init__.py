"""The benchmark of tpunode: see chipbench/README.md."""
