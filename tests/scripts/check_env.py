"""Exit non-zero unless the common injected env contract holds
(ref: exit_0_check_env.py — the job's final status IS the assertion)."""
import json
import os
import sys

required = ["TONY_JOB_NAME", "TONY_TASK_INDEX", "TONY_TASK_NUM", "TONY_IS_CHIEF",
            "CLUSTER_SPEC", "TONY_JOB_ID", "TONY_SESSION_ID",
            "TONY_JOB_DIR"]
missing = [k for k in required if k not in os.environ]
if missing:
    print("missing env:", missing)
    sys.exit(1)

spec = json.loads(os.environ["CLUSTER_SPEC"])
role = os.environ["TONY_JOB_NAME"]
idx = int(os.environ["TONY_TASK_INDEX"])
if role not in spec or idx >= len(spec[role]):
    print("bad spec", spec, role, idx)
    sys.exit(2)
if not spec[role][idx]:
    print("own entry empty in spec", spec)
    sys.exit(3)
sys.exit(0)
