# usage: readings.sh <cell> <seconds> <seed,seed,seed> ; the program's and the controls' readings of a
# cell's compared numbers (control.py), one JSON line a seed, kept under chiprun_out/
cell=$1; secs=$2; seeds=$3
python3 benchmark/control.py --workload $cell --seeds $seeds --seconds $secs \
  2> chiprun_out/ctl_${cell}.err > chiprun_out/ctl_${cell}.out
echo "$cell control rc $?"
grep -h "set-up\|embedding gaps\|answers compared" chiprun_out/ctl_${cell}.err | cut -c1-220
python3 - "$cell" <<'PY'
import json, sys
for line in open(f"chiprun_out/ctl_{sys.argv[1]}.out"):
    d = json.loads(line)
    print("seed", d["seed"])
    for name, v in d.items():
        if name == "seed":
            continue
        gaps = {k: x for k, x in v["numbers"].items() if "gap" in k or "off" in k}
        print(f"  {name}: correct {v['correct']} failed {v['failed']} {gaps} widest {v.get('embed_gap_widest')}")
PY
