#!/bin/sh
# The sandbox rehearsal: every cell of BENCHMARK.json on a CPU server at
# 8 shards, both trace modes, and the last line's keys asserted.  Run from
# the root of the checkout before each chip call.  Not a measurement: it
# prints "platform": "cpu", and no number of it is a device number.
set -e
cd "$(dirname "$0")/.."
for cell in $(python3 -c "import json; print(' '.join(w['name'] for w in json.load(open('BENCHMARK.json'))['workloads']))"); do
  for trace in 0 1; do
    echo "== $cell --trace $trace" >&2
    python3 benchmark/run.py --workload "$cell" --seed 2147483777 --seconds 12 \
      --trace $trace --rehearse 2>/dev/null | tail -n 1 | python3 -c "
import json, sys
bench = json.load(open('BENCHMARK.json'))
line = json.loads(sys.stdin.read())
assert list(line)[-1] == 'checks', list(line)
for key in ('correct', 'attempted', 'failed', 'metrics', 'device'):
    assert key in line, key
assert line['correct'] is True and line['failed'] == 0, line['checks']
assert line['device']['platform'] == 'cpu'
group = 'per_layer' if $trace else 'end_to_end'
known = {m['name'] for m in bench[group]}
assert line['metrics'] and set(line['metrics']) <= known, set(line['metrics']) - known
if $trace:
    assert 0 < line['device']['busy_s'] <= line['device']['window_s']
    assert line['breakdown']['device_ops']
else:
    assert set(line['metrics']) == known
print('ok', '$cell', 'trace', $trace, sorted(line['metrics']))
"
  done
done
