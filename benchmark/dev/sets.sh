# usage: [SEED_SHIFT=n] sets.sh <cell> <seconds> <runs-per-set> [traced runs] ; two sets with the same seeds, then traced runs
cell=$1; secs=$2; n=$3; traced=${4:-3}; shift_=${SEED_SHIFT:-0}; mkdir -p chiprun_out
for set in A B; do
  for i in $(seq 1 $n); do
    seed=$((2147480000 + shift_ + i * 7919))
    python3 benchmark/run.py --workload $cell --seed $seed --seconds $secs --trace 0 2> chiprun_out/set_${cell}_${set}${i}.err | tail -1 > chiprun_out/set_${cell}_${set}${i}.json
    echo "$cell set $set run $i seed $seed rc $? $(cut -c1-420 chiprun_out/set_${cell}_${set}${i}.json)"
    grep "set-up\|embedding gaps\|answers compared" chiprun_out/set_${cell}_${set}${i}.err | cut -c1-200
    grep "compared" chiprun_out/set_${cell}_${set}${i}.err | grep -v "= 0 (" | cut -c1-200
  done
done
for i in $(seq 1 $traced); do
  seed=$((2147400000 + shift_ + i * 104729))
  python3 benchmark/run.py --workload $cell --seed $seed --seconds $secs --trace 1 2> chiprun_out/tr_${cell}_${i}.err | tail -1 > chiprun_out/tr_${cell}_${i}.json
  echo "$cell traced run $i seed $seed rc $? $(cut -c1-2600 chiprun_out/tr_${cell}_${i}.json)"
  grep "set-up\|embedding gaps\|answers compared\|per-layer metric" chiprun_out/tr_${cell}_${i}.err | cut -c1-200
  grep "compared" chiprun_out/tr_${cell}_${i}.err | grep -v "= 0 (" | cut -c1-200
done
