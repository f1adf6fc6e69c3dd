# Measure a cell on the chip, one process per step: the knee sweep (which
# sets the cell's rate), the correctness calibration (which sets its
# limit), two sets of N runs on the same seeds at the benchmark's
# run_seconds, and three traced runs. Outputs go to OUT/; a sweep or a
# calibration that sets nothing ends the script. Given no rates, the
# sweep and the calibration are left out and the cell file stands.
#   bash bench/tools/cell.sh OUT WORKLOAD TAG SEEDPREFIX N [RATE ...]
set -u
OUT=$1; W=$2; T=$3; S=$4; N=$5; shift 5
SECS=$(python3 -c 'import json; print(json.load(open("BENCHMARK.json"))["run_seconds"])')
mkdir -p "$OUT"
if [ $# -gt 0 ]; then
timeout 1200 python3 bench/tools/sweep.py $W 30 ${S}11 "$@" > $OUT/${T}_sweep.jsonl 2> $OUT/${T}_sweep.err
echo "$T sweep rc=$?"
cat $OUT/${T}_sweep.jsonl; tail -3 $OUT/${T}_sweep.err
python3 bench/tools/pick.py rate $W < $OUT/${T}_sweep.jsonl | tee -a $OUT/picks.jsonl
[ "${PIPESTATUS[0]}" = 0 ] || exit 1
timeout 1200 python3 bench/tools/calibrate.py $W 20 ${S}21 ${S}22 ${S}23 ${S}24 ${S}25 ${S}26 ${S}27 ${S}28 ${S}29 ${S}30 ${S}31 ${S}32 > $OUT/${T}_cal.jsonl 2> $OUT/${T}_cal.err
echo "$T cal rc=$?"
cat $OUT/${T}_cal.jsonl; tail -3 $OUT/${T}_cal.err
python3 bench/tools/pick.py limit $W < $OUT/${T}_cal.jsonl | tee -a $OUT/picks.jsonl
[ "${PIPESTATUS[0]}" = 0 ] || exit 1
fi
cp bench/cells/$W.json $OUT/$W.cell.json
for set in 1 2; do
  for k in $(seq 41 $((40 + N))); do
    timeout 400 python3 bench/run.py --workload $W --seed ${S}$k --seconds $SECS --trace 0 > $OUT/${T}_set${set}_$k.out 2> $OUT/${T}_set${set}_$k.err
    echo "$T set$set seed ${S}$k rc=$?"; tail -n 1 $OUT/${T}_set${set}_$k.out | cut -c1-700
  done
done
for k in 51 52 53; do
  timeout 400 python3 bench/run.py --workload $W --seed ${S}$k --seconds $SECS --trace 1 > $OUT/${T}_trace_$k.out 2> $OUT/${T}_trace_$k.err
  echo "$T trace seed ${S}$k rc=$?"; tail -n 1 $OUT/${T}_trace_$k.out | cut -c1-2500
done
