def read(obs, params):
    return obs["marks"]["end"]["compiles"] - obs["marks"]["start"]["compiles"]
