#!/bin/sh
# Round-5 chip rows that were never captured (ROADMAP S1 folds them into
# the benchmark's cell table). Every command prints one JSON line or a
# hunt summary. `python chip_smoke.py` runs rows 2 and 4 cut to size.
#
# Serialize everything: a chip belongs to one process at a time.
set -x

# 1. Flagship bench
python bench.py

# 2. Hunt end-to-end at high find rate — the directive-3 "done" bar:
#    clean multipaxos streams ~2.9k seeds/s on chip; the hunt should now
#    be within a few percent of that (was 296 seeds/s before the
#    compiled-replay fix)
time python -m madsim_tpu hunt --machine demo-nopromise-multipaxos \
  --seeds 106000 --stream --batch 8192 --horizon 8 --queue 96 --faults 3 \
  --fault-kinds pair,kill,dir,group,storm --fault-tmax 3000000 \
  --max-steps 6000 --corpus /tmp/chip_corpus.json --limit 3

# 3. Clean-rate guard for the same machine (directive 3: "clean-run
#    number unharmed")
python -m madsim_tpu bench --machine multipaxos --lanes 8192 --seeds 106000 \
  --reps 3 --horizon 8 --queue 96 --faults 3 \
  --fault-kinds pair,kill,dir,group,storm --fault-tmax 3000000 --max-steps 6000

# 4. Gossip 33-node at 100k seeds, full vocabulary incl. delay
#    (directive 6: the larger-n PROFILE row)
python -m madsim_tpu bench --machine gossip --nodes 33 --lanes 8192 \
  --seeds 100000 --reps 1 --horizon 5 --queue 320 --faults 3 \
  --fault-kinds pair,kill,dir,group,storm,delay --fault-tmax 3000000 \
  --max-steps 9000

# 5. S3 machine at 100k seeds (directive 4's chip row)
python -m madsim_tpu bench --machine s3 --nodes 4 --lanes 8192 \
  --seeds 100000 --reps 1 --horizon 8 --queue 48 --faults 3 \
  --fault-kinds pair,kill,dir,group,storm,delay --fault-tmax 3000000 \
  --max-steps 4000

# 6. Delay-exclusive bug class at scale (directive 5's find-rate row)
python -m madsim_tpu explore --machine demo-giveup-mvcc --seeds 100000 \
  --stream --batch 8192 --horizon 8 --queue 48 --faults 3 \
  --fault-kinds delay --fault-tmax 3000000 --max-steps 3000
