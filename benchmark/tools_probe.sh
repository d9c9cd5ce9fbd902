#!/bin/bash
# the looks by hand behind PERF.md section 2 and 5: a long probed window
# of each training cell and a probed serving window (--probe: freezes of
# this process, device memory sampled together), then each cell's traced
# run: chiprun --chips 1 --timeout 1500 -- bash benchmark/tools_probe.sh [seconds]
long=${1:-150}
mkdir -p chiprun_out/probe
run() {
  name=$1; shift
  python3 benchmark/run.py "$@" > chiprun_out/probe/$name.log 2>&1
  echo "== $name rc $?"
  grep -E "^probe|^memory|SLOW|^window|^first tokens|^gaps|OVER" chiprun_out/probe/$name.log | cut -c1-600
  tail -n 1 chiprun_out/probe/$name.log | cut -c1-2500
}
run gpt2m-train.probe --workload gpt2m-train --seed 2400000011 --seconds $long --trace 0 --probe
run resnet50-train.probe --workload resnet50-train --seed 2400000012 --seconds $long --trace 0 --probe
run gpt2m-serve-chat.probe --workload gpt2m-serve-chat --seed 2400000013 --seconds 90 --trace 0 --probe
run gpt2m-train.trace --workload gpt2m-train --seed 2400000014 --seconds 20 --trace 1
run resnet50-train.trace --workload resnet50-train --seed 2400000015 --seconds 20 --trace 1
run gpt2m-serve-chat.trace --workload gpt2m-serve-chat --seed 2400000016 --seconds 45 --trace 1
