import sys
sys.exit("exit_with_reason: the task refuses to run and says why")
